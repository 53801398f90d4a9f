"""The check catches a broken timed path: each fault is planted under the
engine of a CPU run at the tiny size, and ``correct`` must come out false.

Faults a one-chip serving cell can have: a verify step that returns the
pool unchanged; half of a batch left out (those requests get an answer
nobody computed); a token altered where it is produced.  (No cell spans
chips, so there is no exchange between chips to leave out.)"""
import dataclasses
import time

import pytest

from helpers import tiny_spec
import run


def state_unchanged(engine):
    steps = engine.core.steps
    verify = steps.verify
    steps.verify = lambda params, pool, slots, vb: (verify(params, pool, slots, vb)[0], pool)


def half_batch_left_out(engine):
    """The step verifies the first ``n // 2`` requests of a batch of ``n``;
    the rest get an answer nobody computed (token 0, one token a round)."""
    core = engine.core
    verify = core.verify

    def patched(slots, prev, toks, qs, lens):
        res, bucket, secs = verify(slots, prev, toks, qs, lens)
        n = slots.shape[0]
        left = slice(n // 2, n)
        res = dataclasses.replace(
            res,
            out_tokens=res.out_tokens.at[left].set(-1).at[left, 0].set(0),
            n_commit=res.n_commit.at[left].set(1),
            n_accepted=res.n_accepted.at[left].set(0),
            extra_token=res.extra_token.at[left].set(0),
        )
        return res, bucket, secs

    core.verify = patched


def token_altered(engine):
    core = engine.core
    verify = core.verify
    vocab = engine.model.cfg.vocab_size

    def patched(*a):
        res, bucket, secs = verify(*a)
        return dataclasses.replace(res, out_tokens=(res.out_tokens + 1) % vocab), bucket, secs

    core.verify = patched


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out, token_altered])
def test_fault_is_not_correct(fault):
    res = run.run(tiny_spec(rate=8.0), seed=77, seconds=4.0, trace=False,
                  t_start=time.monotonic(), patch=fault)
    assert not res["correct"], res["check"]
