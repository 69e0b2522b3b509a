"""As ``fft.traces_per_call``, in the 4-chip cell, where it moves ``fft_ms.p4``."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft.traces_per_call.py").read
