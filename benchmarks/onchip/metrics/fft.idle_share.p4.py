"""As ``fft.idle_share``, in the 4-chip cell, where it moves ``fft_ms.p4``."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft.idle_share.py").read
