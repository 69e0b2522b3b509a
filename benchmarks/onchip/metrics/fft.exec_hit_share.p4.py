"""As ``fft.exec_hit_share``, in the 4-chip cell, where it moves ``fft_ms.p4``."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft.exec_hit_share.py").read
