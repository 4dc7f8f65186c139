"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a small cell on the CPU (everything but
the harness's look for a chip) with ``TailServer.step`` broken in one way
a served cell can be broken, and checks that ``correct`` is false.  The
sound run beside them is correct."""
import numpy as np
import pytest

import chipbench_testkit as tk
from chipbench.harness import run_cell
from chipbench.spec import Spec


@pytest.fixture(scope="module")
def spec(tmp_path_factory):
    return Spec.load(tk.make_checkout(str(tmp_path_factory.mktemp("co"))))


def run(spec, seed=2 ** 31 + 11):
    return run_cell(spec, "tiny-vgg.poisson", seed, 0.5, False,
                    require_tpu=False, compile_cache=False, log=lambda m: None)


def alter_one_answer(out, state):
    """One logit of one answer altered where it is produced."""
    if out:
        rid = next(iter(out))
        got = np.array(out[rid], copy=True)
        got.flat[0] += 0.05 * np.abs(got).max()
        out[rid] = got
    return out


def swap_slots(out, state):
    """Two requests' answers trade slots."""
    rids = list(out)
    if len(rids) >= 2:
        out[rids[0]], out[rids[-1]] = out[rids[-1]], out[rids[0]]
    return out


def half_batch_left_out(out, state):
    """The upper half of the active slots is left out of the computation:
    those requests get zeros."""
    rids = list(out)
    for rid in rids[len(rids) // 2:]:
        out[rid] = np.zeros_like(out[rid])
    return out


def stale_state(out, state):
    """The step hands back the previous step's logits, its state
    unchanged."""
    prev = state.get("prev")
    state["prev"] = [out[r] for r in out]
    if prev:
        for k, rid in enumerate(out):
            out[rid] = prev[k % len(prev)]
    return out


def drop_one(out, state):
    """A request that never gets an answer."""
    window = [rid for rid in out if rid >= 0]     # warm-up ids are negative
    if window and not state.get("dropped"):
        out.pop(window[0])
        state["dropped"] = True
    return out


def test_sound_run_is_correct(spec):
    r = run(spec)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] == 500
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [alter_one_answer, swap_slots,
                                   half_batch_left_out, stale_state, drop_one])
def test_broken_timed_path_is_not_correct(spec, monkeypatch, fault):
    from repro.runtime.engine import TailServer

    step = TailServer.step
    state = {}

    def broken(self, *a, **kw):
        return fault(dict(step(self, *a, **kw)), state)

    monkeypatch.setattr(TailServer, "step", broken)
    r = run(spec)
    assert not r["correct"]
    assert r["failed"] >= 1
