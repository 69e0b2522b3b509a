"""As ``fft.mfu``, in the 4-chip cell, where it moves ``fft_ms.p4``."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft.mfu.py").read
