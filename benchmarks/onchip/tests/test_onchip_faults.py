"""A run whose timed path is broken underneath comes out not correct:
once for each fault a cell can have."""

import jax.numpy as jnp
import numpy as np
import pytest

import repro.algorithms
from repro.core import LPFContext
from drive import drive


def _altered(orig):
    def fft(mesh, x, **kw):
        y = orig(mesh, x, **kw)
        return y.at[1].multiply(2.0)
    return fft


def _half_batch(orig):
    def fft(mesh, x, **kw):
        keep = (jnp.arange(x.shape[0]) % 2 == 0).astype(x.dtype)
        return orig(mesh, x * keep * 2, **kw)
    return fft


def _unchanged(orig):
    def fft(mesh, x, **kw):
        return x
    return fft


def _pr_unchanged(orig):
    def pagerank(mesh, g, **kw):
        return orig(mesh, g, **{**kw, "max_iter": 0})
    return pagerank


def _pr_half_batch(orig):
    def pagerank(mesh, g, **kw):
        vals = np.array(g.vals)
        vals[:, 1::2] = 0.0
        vals[:, ::2] *= 2.0
        g2 = type(g)(**{**g.__dict__, "vals": vals})
        return orig(mesh, g2, **kw)
    return pagerank


def _pr_altered(orig):
    def pagerank(mesh, g, **kw):
        r, iters, res = orig(mesh, g, **kw)
        return r.at[int(jnp.argmax(r))].multiply(1.5), iters, res
    return pagerank


FAULTS = [
    ("fft-c64-2e28.p1", "bsp_fft", _altered),
    ("fft-c64-2e28.p1", "bsp_fft", _half_batch),
    ("fft-c64-2e28.p1", "bsp_fft", _unchanged),
    ("fft-c64-2e28.p4", "bsp_fft", _altered),
    ("fft-c64-2e28.p4", "bsp_fft", _half_batch),
    ("fft-c64-2e28.p4", "bsp_fft", _unchanged),
    ("pagerank-rmat-s20.p1", "lpf_pagerank", _pr_unchanged),
    ("pagerank-rmat-s20.p1", "lpf_pagerank", _pr_half_batch),
    ("pagerank-rmat-s20.p1", "lpf_pagerank", _pr_altered),
]


@pytest.mark.parametrize("workload,entry,fault", FAULTS,
                         ids=[f"{w}-{f.__name__.strip('_')}"
                              for w, _, f in FAULTS])
def test_fault_is_caught(monkeypatch, workload, entry, fault):
    orig = getattr(repro.algorithms, entry)
    monkeypatch.setattr(repro.algorithms, entry, fault(orig))
    res = drive(workload)
    assert res["checks"] and not res["correct"], res["checks"]


def test_exchange_left_out_is_caught(monkeypatch):
    """The 4-chip FFT with its messages dropped before they are sent."""
    monkeypatch.setattr(LPFContext, "put_msgs",
                        lambda self, msgs, *a, **kw: None)
    res = drive("fft-c64-2e28.p4")
    assert res["checks"] and not res["correct"], res["checks"]
