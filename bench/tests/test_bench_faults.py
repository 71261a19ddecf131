"""A run whose timed path is broken underneath comes out not correct: an
answer altered where the program produces it (one output sample of a
stream call; the last token of every served request)."""
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import smoke


def _break_stream(monkeypatch):
    from repro.core.program import Program
    real = Program.stream

    def stream(self, feeds, **kw):
        out = real(self, feeds, **kw)
        return {k: v.at[3, 0, 1, 5].add(0.5) for k, v in out.items()}

    monkeypatch.setattr(Program, "stream", stream)


def _break_serve(monkeypatch):
    from repro.serve import ActorEngine
    real = ActorEngine.generate

    def generate(self, requests, **kw):
        out = real(self, requests, **kw)
        for r in out:
            r.tokens = r.tokens.copy()
            r.tokens[-1] = (r.tokens[-1] + self.cfg.vocab // 2) % self.cfg.vocab
        return out

    monkeypatch.setattr(ActorEngine, "generate", generate)


BREAK = {"stream": _break_stream, "serve": _break_serve}


@pytest.mark.parametrize("workload", [w["name"] for w in
                                      harness.load_spec()["workloads"]])
def test_altered_answer_is_not_correct(workload, monkeypatch):
    cell = smoke.cell(workload)
    BREAK[cell.config["driver"]](monkeypatch)
    line = harness.run(cell, 2**31 + 11, 0.3, False, time.perf_counter(),
                       require_chip=False)
    assert line["correct"] is False
    assert all(np.isfinite(c["value"]) for c in line["checks"].values())
