"""Faults planted in the timed path, to show that the check fails them.

Each fault takes the built ``GroupRuntime`` and wraps the train step it
is about to compile, so the window's own call and feed run broken:

* ``unchanged``: the step returns the adapters and optimizer state it
  was given (losses still computed).
* ``half_batch``: every other row's ``loss_mask`` is cleared, so each
  job's loss and gradient are the mean over half its rows.
* ``answer_altered``: one leaf of one job's answer (the first job's
  ``A`` of the q projection, every layer) is zeroed as the step
  returns it.  Nothing of the step's input is kept past it, so the
  step's buffers are donated as in the sound run.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp


def _wrap_step(rt, wrap: Callable) -> None:
    make = rt.ssm.make_train_step

    def patched(**kw):
        return wrap(make(**kw))
    rt.ssm.make_train_step = patched


def unchanged(rt) -> None:
    def wrap(fn):
        def step(params, adapters, opt_state, batches):
            _, _, metrics = fn(params, adapters, opt_state, batches)
            return adapters, opt_state, metrics
        return step
    _wrap_step(rt, wrap)


def half_batch(rt) -> None:
    def wrap(fn):
        def step(params, adapters, opt_state, batches):
            b = dict(batches)
            rows = b["loss_mask"].shape[1]
            keep = (jnp.arange(rows) % 2 == 0).astype(b["loss_mask"].dtype)
            b["loss_mask"] = b["loss_mask"] * keep[None, :, None]
            return fn(params, adapters, opt_state, b)
        return step
    _wrap_step(rt, wrap)


def answer_altered(rt) -> None:
    off, rp = rt.ssm.layout.slice_of(0)

    def wrap(fn):
        def step(params, adapters, opt_state, batches):
            new, opt, metrics = fn(params, adapters, opt_state, batches)
            new = jax.tree.map(lambda x: x, new)
            qa = new["segments"][0]["0"]["q"]
            qa["A"] = qa["A"].at[..., off:off + rp].set(0)
            return new, opt, metrics
        return step
    _wrap_step(rt, wrap)


FAULTS: Dict[str, Callable] = {"unchanged": unchanged,
                               "half_batch": half_batch,
                               "answer_altered": answer_altered}
