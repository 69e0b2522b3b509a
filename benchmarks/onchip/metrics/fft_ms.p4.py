"""As ``fft_ms``, in the 4-chip cell: milliseconds per transform, the
whole window over the transforms completed in it."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft_ms.py").read
