"""The program's host spans in a profiler trace (``repro.core.trace``
``SPAN_*``): which spans ``Program.stream``, ``Program.run`` and
``ActorEngine.generate`` open, how often, how they nest, under the names
the benchmark's readers look for (``bench.lib.spans``), and that opening
them changes no output."""
import jax
import numpy as np
import pytest

from bench.lib import spans, trace_reduce
from repro.configs import smoke_config
from repro.core import ExecutionPlan, trace
from repro.core.faultinject import POISON_VALUE
from repro.graphs.factories import make_dpd
from repro.models import init_params
from repro.serve import ActorEngine, Request, ServeConfig

CHUNK = 2
STREAM_SPANS = {spans.STREAM, spans.PREPARE, spans.STAGE, spans.LAUNCH,
                spans.WAIT, spans.DECODE, spans.COLLECT}


def traced(tmp_path, fn):
    """``fn()`` under the profiler, as the benchmark traces it; returns its
    result and the program spans of the reduced trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            out = fn()
    s = trace_reduce.summarize(trace_reduce.find_xspace(str(tmp_path)))
    return out, [e for e in s.host
                 if e.name.startswith(("Program.", "ActorEngine."))]


def count(evs, name):
    return sum(e.name == name for e in evs)


@pytest.fixture(scope="module")
def dpd_stream():
    net, n = make_dpd()
    accel = tuple(a for a in net.actors if a not in ("source", "sink"))
    prog = net.compile(mode="dynamic", n_iterations=CHUNK, accelerated=accel)
    L = 256
    sig = np.random.default_rng(0).normal(
        size=(2, n * L)).astype(np.float32)
    wins = np.stack([sig[:, i * L:(i + 1) * L] for i in range(n)])[:, None]
    return prog, {"f_in": wins}, n // CHUNK


@pytest.fixture(scope="module", params=[False, True],
                ids=["chunked", "persistent"])
def streamed(request, dpd_stream, tmp_path_factory):
    """(untraced, traced) readings of one stream call, and its spans."""
    prog, feeds, n_chunks = dpd_stream
    persistent = request.param

    def call():
        out = prog.stream(feeds, persistent=persistent)
        return {"outputs": np.asarray(out["f_out"]),
                "sweeps": prog.last_stream_sweeps,
                "fire_counts": dict(prog.last_stream_fire_counts)}

    plain = call()
    got, evs = traced(tmp_path_factory.mktemp("trace"), call)
    return persistent, n_chunks, plain, got, evs


def test_span_names_are_the_benchmarks():
    assert (trace.SPAN_STREAM, trace.SPAN_PREPARE, trace.SPAN_STAGE,
            trace.SPAN_LAUNCH, trace.SPAN_WAIT, trace.SPAN_DECODE,
            trace.SPAN_COLLECT) == spans.PROGRAM
    assert trace.SPAN_BUILD == spans.BUILD
    assert set(spans.HOST_PHASES) == set(spans.PROGRAM) - {
        spans.STREAM, spans.WAIT}


@pytest.mark.parametrize("part", ["outputs", "sweeps", "fire_counts"])
def test_spans_change_no_output(streamed, part):
    _, _, plain, got, _ = streamed
    if part == "outputs":
        np.testing.assert_array_equal(plain[part], got[part])
    else:
        assert plain[part] == got[part]


def test_stream_opens_exactly_its_spans(streamed):
    *_, evs = streamed
    assert {e.name for e in evs} == STREAM_SPANS


@pytest.mark.parametrize("name", sorted(STREAM_SPANS))
def test_stream_span_counts(streamed, name):
    """One ``Program.stream`` and ``prepare`` per call; ``stage``, the
    run's ``launch``/``wait``/``decode`` and ``collect`` once per chunk
    (one full-length chunk when persistent), and one more ``collect``
    for the chunked loop's concatenation of the outputs."""
    persistent, n_chunks, _, _, evs = streamed
    chunks = 1 if persistent else n_chunks
    want = {spans.STREAM: 1, spans.PREPARE: 1, spans.STAGE: chunks,
            spans.LAUNCH: chunks, spans.WAIT: chunks, spans.DECODE: chunks,
            spans.COLLECT: chunks + (0 if persistent else 1)}[name]
    assert count(evs, name) == want


def test_stream_spans_nest_in_the_call(streamed):
    *_, evs = streamed
    (call,) = [e for e in evs if e.name == spans.STREAM]
    for e in evs:
        assert call.start_ns <= e.start_ns <= e.end_ns <= call.end_ns, e
    # The phases follow each other: none overlaps another.
    phases = sorted((e for e in evs if e.name != spans.STREAM),
                    key=lambda e: e.start_ns)
    for a, b in zip(phases, phases[1:]):
        assert a.end_ns <= b.start_ns, (a, b)


@pytest.fixture(scope="module")
def lm():
    cfg = smoke_config("granite-8b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("poisoned,attempts", [(False, 1), (True, 2)],
                         ids=["clean", "quarantine-retry"])
def test_generate_opens_a_build_span_per_attempt(lm, tmp_path, poisoned,
                                                 attempts):
    cfg, params = lm
    rng = np.random.default_rng(3)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=4)
                    .astype(np.int32), max_new=3) for _ in range(3)]
    if poisoned:
        reqs[1] = Request(prompt=np.full(4, POISON_VALUE, np.int32),
                          max_new=3)
    eng = ActorEngine(cfg, params,
                      ServeConfig(batch_size=2, max_prompt=8, max_new=4),
                      plan=ExecutionPlan(mode="dynamic", guards=poisoned))
    _, evs = traced(tmp_path, lambda: eng.generate(
        reqs, on_fault="quarantine" if poisoned else "raise"))
    assert eng.last_retries == attempts - 1
    assert count(evs, spans.BUILD) == attempts
    assert count(evs, spans.LAUNCH) == attempts
    assert {e.name for e in evs} == {spans.BUILD, spans.LAUNCH, spans.WAIT,
                                     spans.DECODE}
