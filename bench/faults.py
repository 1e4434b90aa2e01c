"""Faults planted underneath a run, to show that ``correct`` catches them.

Each is a context manager that breaks the program's timed path for the
duration of the ``with`` block, without touching the harness:

- ``frozen_state``: every ``train_step`` returns the state it was given;
- ``half_batch``: in every group, the second half of the rows repeats the
  first, so the mean is taken over half the batch;
- ``no_exchange``: the exchange between chips is left out: each group's
  outer step takes its own change instead of the mean over the groups;
- ``altered_loss``: the loss ``train_step`` returns is 1% off.

``bench/tests/test_faults.py`` drives a run under each on the CPU, and
``bench/calibrate.py`` reads them on the chip at a cell's own size.
"""

from __future__ import annotations

import contextlib

FAULTS = ("frozen_state", "half_batch", "no_exchange", "altered_loss")


@contextlib.contextmanager
def _patched(obj, name, make):
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _frozen(orig):
    import jax

    def train_step(self, batch):
        keep = jax.tree.map(lambda x: x.copy(), (self.state, self.outer))
        out = orig(self, batch)
        self.state, self.outer = keep
        return out
    return train_step


def _altered(orig):
    def train_step(self, batch):
        out = orig(self, batch)
        return dict(out, loss=out["loss"] * 1.01)
    return train_step


def _half_batch(orig):
    def init(self, mesh, batch_axes, make_batch, **kw):
        groups = mesh.shape["data_outer"]

        def halved(step):
            b = make_batch(step)
            out = {}
            for k, v in b.items():
                v = v.copy()
                per = v.shape[0] // groups
                for g in range(groups):
                    lo, half = g * per, per // 2
                    v[lo + half:lo + 2 * half] = v[lo:lo + half]
                out[k] = v
            return out
        orig(self, mesh, batch_axes, halved, **kw)
    return init


def _unchecked_outer(orig):
    """``compat.shard_map`` with the varying-axes check off for the outer
    step alone: its outer state, typed as the same on every group, then
    holds each group's own."""
    import jax

    def shard_map(f, *, mesh, in_specs, out_specs, axis_names):
        if f.__name__ != "outer_body":
            return orig(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                        axis_names=axis_names)
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, axis_names=set(axis_names),
                             check_vma=False)
    return shard_map


def _local_reduce(_orig):
    def reduce_leaf(self, d, r, tc, ctx):
        return d, r
    return reduce_leaf


@contextlib.contextmanager
def plant(name: str):
    """Break the program's timed path with fault ``name`` (see FAULTS)."""
    if name in ("frozen_state", "altered_loss"):
        from repro.launch.train import Trainer
        make = _frozen if name == "frozen_state" else _altered
        with _patched(Trainer, "train_step", make):
            yield
    elif name == "half_batch":
        from repro.data.pipeline import DataPipeline
        with _patched(DataPipeline, "__init__", _half_batch):
            yield
    elif name == "no_exchange":
        from repro import compat
        from repro.sync.strategies import FlatFP32
        with _patched(compat, "shard_map", _unchecked_outer), \
                _patched(FlatFP32, "reduce_leaf", _local_reduce):
            yield
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
