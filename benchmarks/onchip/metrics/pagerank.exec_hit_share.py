"""As ``fft.exec_hit_share``, per PageRank solve, where it moves
``pagerank_iter_ms``."""

import cells

read = cells.load_module(cells.HERE / "metrics" / "fft.exec_hit_share.py").read
