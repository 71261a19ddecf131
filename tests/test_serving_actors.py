"""Continuous-batching serving actors: identity vs the legacy Engine,
rate-0 idle firings, re-admission, declared-bound verdicts, early stop."""
import contextlib

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import ExecutionPlan
from repro.graphs.serving import (ServingWorkload, build_serving_network,
                                  left_pad_prompts, poisson_trace)
from repro.models import init_params
from repro.serve import ActorEngine, Engine, Request, ServeConfig
from repro.serve import actor_engine


@pytest.fixture(scope="module")
def lm():
    cfg = smoke_config("granite-8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def requests(lm):
    cfg, _ = lm
    rng = np.random.default_rng(1)
    return [Request(prompt=rng.integers(1, cfg.vocab,
                                        size=int(n)).astype(np.int32),
                    max_new=m)
            for n, m in [(5, 4), (3, 2), (7, 4), (4, 3), (6, 4)]]


@pytest.fixture(scope="module")
def scfg():
    # eos_id inside the argmax range so some slots retire via EOS and
    # others via budget — both rate-0 paths exercised.
    return ServeConfig(batch_size=2, max_prompt=8, max_new=4, eos_id=7)


@pytest.fixture(scope="module")
def legacy_tokens(lm, requests, scfg):
    cfg, params = lm
    return [r.tokens for r in Engine(cfg, params, scfg).generate(requests)]


# --------------------------------------------------------------------------- #
# Token-for-token identity oracle (ISSUE acceptance: both plans, guards
# on and off).
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode,guards", [
    ("dynamic", False), ("dynamic", True),
    ("megakernel", False), ("megakernel", True),
])
def test_actor_engine_matches_legacy(lm, requests, scfg, legacy_tokens,
                                     mode, guards):
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg,
                      plan=ExecutionPlan(mode=mode, guards=guards))
    got = eng.generate(requests)
    for want, have in zip(legacy_tokens, got):
        np.testing.assert_array_equal(want, have.tokens)
    # Every actor fires once per admission sweep — the idle/EOS firings
    # are real (control token consumed) rate-0 firings, not skips.
    counts = eng.last_fire_counts
    assert counts["decode"] == counts["admission"] == counts["merge"]


def test_admission_timing_does_not_change_tokens(lm, requests, scfg,
                                                 legacy_tokens):
    """Open-loop arrivals delay admission but never change a request's
    greedy tokens (dense rows are batch-independent)."""
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg)
    got = eng.generate(requests, arrivals=np.array([0, 1, 2, 5, 9],
                                                   np.int32))
    for want, have in zip(legacy_tokens, got):
        np.testing.assert_array_equal(want, have.tokens)
    lat = eng.last_latency_steps
    assert lat is not None and (lat >= 0).all()


# --------------------------------------------------------------------------- #
# Rate-0 firings and re-admission.
# --------------------------------------------------------------------------- #
def test_idle_steps_are_rate0_firings_in_fire_counts(lm, scfg):
    """An arrival gap leaves steps with no active slot: decode still
    fires (consuming its control token, body skipped), so its fire count
    exceeds the number of tokens it produced."""
    cfg, params = lm
    reqs = [Request(prompt=np.array([3, 4, 5], np.int32), max_new=2),
            Request(prompt=np.array([6, 8, 9], np.int32), max_new=2)]
    eng = ActorEngine(cfg, params, scfg)
    got = eng.generate(reqs, arrivals=np.array([0, 6], np.int32))
    total_tokens = sum(len(r.tokens) for r in got)
    assert eng.last_fire_counts["decode"] > total_tokens
    # The retire sink fired every sweep too — most of them rate-0.
    assert eng.last_fire_counts["retire"] == eng.last_fire_counts["decode"]


def test_no_request_starves_under_bursty_arrivals(lm, scfg):
    """R >> B with a bursty Poisson trace: every freed slot is re-admitted
    and every request eventually retires with its full budget."""
    cfg, params = lm
    rng = np.random.default_rng(3)
    R = 7                                   # vs batch_size=2
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=4)
                    .astype(np.int32), max_new=3) for _ in range(R)]
    arrivals = poisson_trace(R, rate=1.5, seed=11)
    eng = ActorEngine(cfg, params, scfg)
    got = eng.generate(reqs, arrivals=arrivals)
    assert len(got) == R
    for r, res in zip(reqs, got):
        assert 1 <= len(res.tokens) <= r.max_new
    assert (eng.last_latency_steps >= 1).all()


# --------------------------------------------------------------------------- #
# One program per live request count: later sets of that count are staged
# into the kept program's state, with no compile.
# --------------------------------------------------------------------------- #
def _request_set(cfg, seed, n, idle=()):
    """``n`` requests with fresh prompts, budgets and arrivals; those at
    the indices in ``idle`` ask for no tokens (they never reach the
    network)."""
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab,
                                        size=int(rng.integers(2, 8)))
                    .astype(np.int32),
                    max_new=0 if i in idle else int(rng.integers(1, 5)))
            for i in range(n)]
    return reqs, np.sort(rng.integers(0, 4, n)).astype(np.int32)


@contextlib.contextmanager
def _backend_compiles():
    """Counts JAX's backend compiles while open."""
    seen = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.mark.parametrize("calls", [
    # (seed, requests, indices asking for no tokens) per generate call
    [(11, 3, ()), (12, 3, ())],
    [(11, 3, ()), (13, 4, ()), (14, 3, ())],
    [(11, 3, ()), (15, 5, (0, 3))],
], ids=["same-count", "new-count-then-back", "zero-budget-requests"])
def test_program_kept_per_live_request_count(lm, scfg, calls):
    """A set whose live request count the engine has served runs on the
    kept program with no compile, and its tokens equal a fresh engine's;
    a new count builds one more program."""
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg)
    seen_counts = set()
    for seed, n, idle in calls:
        reqs, arrivals = _request_set(cfg, seed, n, idle)
        live = n - len(idle)
        reused = live in seen_counts
        seen_counts.add(live)
        with _backend_compiles() as compiles:
            got = eng.generate(reqs, arrivals=arrivals)
        assert eng.last_program_reused is reused
        assert eng.programs_built == len(seen_counts)
        if reused:
            assert compiles == []
        want = ActorEngine(cfg, params, scfg).generate(reqs,
                                                       arrivals=arrivals)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w.tokens, g.tokens)
            assert w.status == g.status
        for i in idle:
            assert got[i].tokens.size == 0


def test_program_cache_drops_least_recently_used(lm, scfg):
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg)
    counts = list(range(1, actor_engine._MAX_PROGRAMS + 2))
    for n in counts:
        eng.generate(_request_set(cfg, n, n)[0])
    assert eng.programs_built == len(counts)
    assert list(eng._programs) == counts[1:]
    eng.generate(_request_set(cfg, 20, counts[1])[0])
    assert eng.last_program_reused
    eng.generate(_request_set(cfg, 21, counts[0])[0])
    assert not eng.last_program_reused
    assert eng.programs_built == len(counts) + 1


# --------------------------------------------------------------------------- #
# Declared bounds: build(check_bounds=True) verdicts pinned.
# --------------------------------------------------------------------------- #
def test_serving_bounds_all_balanced(lm, requests, scfg):
    cfg, params = lm
    slab, lens = left_pad_prompts([r.prompt for r in requests],
                                  scfg.max_prompt)
    wl = ServingWorkload(
        prompts=slab, prompt_lens=lens,
        budgets=np.array([r.max_new for r in requests], np.int32),
        arrivals=np.zeros(len(requests), np.int32))
    _, report = build_serving_network(
        cfg, params, wl, batch_size=scfg.batch_size,
        max_prompt=scfg.max_prompt, max_new=scfg.max_new,
        eos_id=scfg.eos_id, check_bounds=True, return_bounds=True)
    verdicts = {c.fifo: c.verdict for c in report.channels}
    assert verdicts == {
        "fb": "balanced", "table": "balanced", "x": "balanced",
        "fin": "balanced", "xa": "balanced", "y": "balanced",
        "fina": "balanced", "ctl_gate": "balanced",
        "ctl_decode": "balanced", "ctl_merge": "balanced",
        "ctl_retire": "balanced",
    }


# --------------------------------------------------------------------------- #
# Legacy-engine early stop (satellite): fewer decode steps, same tokens.
# --------------------------------------------------------------------------- #
def test_engine_early_stop_same_tokens_fewer_steps(lm):
    cfg, params = lm
    rng = np.random.default_rng(5)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=5)
                    .astype(np.int32), max_new=2) for _ in range(2)]
    base = dict(batch_size=2, max_prompt=8, max_new=8, eos_id=None)
    slow = Engine(cfg, params, ServeConfig(early_stop=False, **base))
    want = slow.generate(reqs)
    assert slow.last_decode_steps == 8 - 1      # the historical fixed loop
    fast = Engine(cfg, params, ServeConfig(early_stop=True, **base))
    got = fast.generate(reqs)
    # Budgets (max_new=2) exhaust after one decode step: 1 vs 7 steps.
    assert fast.last_decode_steps == 1
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)
        assert a.prompt_len == b.prompt_len
