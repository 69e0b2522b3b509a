"""As ``fft.host_ms``, in the 4-chip cell, where it moves ``fft_ms.p4``."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft.host_ms.py").read
