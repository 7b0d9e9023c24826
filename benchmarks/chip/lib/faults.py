"""Faults planted in the program, underneath the timed path, to show that
the check catches them. Each is a context manager that patches the
program's module for as long as it is open; the harness's own runs never
use them.

- ``unchanged``: a step that returns its state unchanged;
- ``half_batch``: half of the batch left out, the mean taken over the rest;
- ``token_altered``: a served token altered where it is produced.

(The exchange between chips does not exist in a one-chip cell.)
"""
from __future__ import annotations

import contextlib
import importlib

import jax


def _half(batch):
    return jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)


@contextlib.contextmanager
def _patched(module, name, make):
    orig = getattr(module, name)
    setattr(module, name, make(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def plant(cell_driver: str, fault: str):
    """The context manager that plants ``fault`` under ``cell_driver``."""
    if cell_driver == "ligo_hop":
        # the module, not the function ``repro.core`` exports by its name
        g = importlib.import_module("repro.core.grow")
        if fault == "unchanged":
            def make(orig):
                def train_ligo(ligo, *a, **k):
                    _, losses = orig(ligo, *a, **k)
                    return ligo, losses
                return train_ligo
            return _patched(g, "train_ligo", make)
        if fault == "half_batch":
            def make(orig):
                def ligo_loss(ligo, small, cfg1, cfg2, batch, **k):
                    return orig(ligo, small, cfg1, cfg2, _half(batch), **k)
                return ligo_loss
            return _patched(g, "ligo_loss", make)
    if cell_driver == "train_step":
        import repro.training.trainer as t
        if fault == "unchanged":
            return _patched(t, "adamw_update",
                            lambda orig: lambda g_, s, p, **k: (p, s))
        if fault == "half_batch":
            def make(orig):
                def loss_fn(params, cfg, batch, **k):
                    return orig(params, cfg, _half(batch), **k)
                return loss_fn
            return _patched(t, "loss_fn", make)
    if cell_driver == "serve_hop" and fault == "token_altered":
        from repro.serving.engine import ServingEngine

        def make(orig):
            def pick(self, req, row):
                tok = orig(self, req, row)
                return (tok + 1) % len(row) if len(req.tokens) % 5 == 2 \
                    else tok
            return pick
        return _patched(ServingEngine, "_pick_token", make)
    raise KeyError(f"no fault {fault!r} for driver {cell_driver!r}")


FAULTS = {"ligo_hop": ("unchanged", "half_batch"),
          "train_step": ("unchanged", "half_batch"),
          "serve_hop": ("token_altered",)}
