"""Faults planted under the timed path, to show that the comparison that
decides ``correct`` catches them. Used by ``chipbench/control.py`` on
the chip and by the CPU tests; never by a benchmark run.

``planted(fault)`` patches the program's classes for as long as it is
open, so a cell built and stepped inside it runs the fault through
``algo.step(t)``:

* ``state_unchanged``: the client update returns the parameters and
  optimizer state it was given;
* ``half_batch``: the client update sees only the first half of the
  private and public batch (of a batch of one sequence, the first half of
  its tokens) and the teachers' rows for them, so every mean is taken
  over the rest;
* ``wire_altered``: each teacher's answers are altered where they are
  produced: the publisher encodes its logits multiplied by 8, as a
  temperature applied twice would.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "wire_altered")


def _half(x, axis: int, keep=None):
    keep = x.shape[axis] // 2 if keep is None else keep
    return x[(slice(None),) * axis + (slice(0, keep),)]


def _state_unchanged(inner):
    def update(params, opt_state, *rest):
        _, _, metrics = inner(params, opt_state, *rest)
        return params, opt_state, metrics
    return update


def _half_batch(inner):
    def update(params, opt_state, priv, pub, teachers, step, rng):
        (batch, *seq), = {v.shape for v in pub.values()}
        if batch > 1:  # images: half of the samples
            axis, rows = 0, teachers["logits"].shape[1] // 2
        else:  # one sequence: half of its tokens, and of its positions
            axis, rows = 1, seq[0] // 2 - 1
        priv = {k: _half(v, axis) if v.ndim > axis else v
                for k, v in priv.items()}
        pub = {k: _half(v, axis) for k, v in pub.items()}
        teachers = {k: _half(v, 2 if k == "aux_logits" else 1, rows)
                    for k, v in teachers.items()}
        return inner(params, opt_state, priv, pub, teachers, step, rng)
    return update


@contextlib.contextmanager
def planted(fault: str):
    from repro.comm.wire import TopKCodec
    from repro.core.runtime import DecentralizedTrainer

    if fault in ("state_unchanged", "half_batch"):
        wrap = _state_unchanged if fault == "state_unchanged" else _half_batch
        owner, attr = DecentralizedTrainer, "_client_update"
        original = owner._client_update

        def patched(self, bundle):
            return wrap(original(self, bundle))
    elif fault == "wire_altered":
        owner, attr = TopKCodec, "encode"
        original = owner.encode

        def patched(self, src, sent_step, t0, sample_ids, outs):
            outs = dict(outs, logits=outs["logits"] * 8,
                        aux_logits=outs["aux_logits"] * 8)
            return original(self, src, sent_step, t0, sample_ids, outs)
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    setattr(owner, attr, patched)
    try:
        yield
    finally:
        setattr(owner, attr, original)
