"""PR 10 serving-resilience chaos suite.

Three fault classes x three planes:

  * **Admission policy** — an expired deadline retires as
    ``status="timeout"`` (partial tokens kept), queue overflow beyond
    ``queue_depth`` sheds as ``status="shed"``; both are rate-0
    admission firings, never health faults, and the survivors' tokens
    are bit-identical to a run without the shed requests.
  * **Quarantine** — a poisoned request trips the slot-table channels'
    DOMAIN write guard; ``ActorEngine.generate(on_fault="quarantine")``
    maps the :class:`NetworkFaultError` back to exactly that request,
    retires it with ``status="fault"``, and re-runs the survivors from
    the pre-run checkpoint with bounded retries — survivor tokens again
    bit-identical.
  * **Durability** — ``stream(checkpoint_dir=...)`` /
    ``run_checkpointed`` commit CRC'd atomically-renamed snapshots; a
    child process is SIGKILLed mid-run and a fresh process resumes from
    the newest intact snapshot, with final outputs, states, fire counts,
    sweeps and the merged trace ring bit-identical to the uninterrupted
    run.  The kill is real (``os.kill(pid, SIGKILL)`` from a snapshot
    hook), not an exception.

The matrix runs on the host dynamic executor, the megakernel, and (in a
subprocess with a forced 8-device host mesh, the test_shard pattern) on
``devices=2``.
"""
import os
import signal
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import (ExecutionPlan, NetworkBuilder, NetworkFaultError,
                        expire_deadline, map_fire, poison_request,
                        static_actor)
from repro.core.faultinject import POISON_VALUE
from repro.models import init_params
from repro.serve import ActorEngine, Request, ServeConfig

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------- #
# Shared serving fixtures (module-scoped: one model init for the file).
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def lm():
    cfg = smoke_config("granite-8b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def scfg():
    return ServeConfig(batch_size=2, max_prompt=8, max_new=4, eos_id=7)


@pytest.fixture(scope="module")
def reqs(lm):
    cfg, _ = lm
    rng = np.random.default_rng(3)
    return [Request(prompt=rng.integers(1, cfg.vocab,
                                        size=int(rng.integers(2, 8)))
                    .astype(np.int32), max_new=4) for _ in range(5)]


@pytest.fixture(scope="module")
def baseline(lm, reqs, scfg):
    """Fault-free oracle tokens (backend-independent by the serving
    bit-identity contract, so one dynamic run serves every cell)."""
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg)
    out = eng.generate(list(reqs))
    assert eng.last_status == ["ok"] * len(reqs)
    return [r.tokens.tolist() for r in out]


def _plan(mode, **kw):
    if mode == "megakernel":
        kw.setdefault("specialize", False)
    return ExecutionPlan(mode=mode, **kw)


# --------------------------------------------------------------------------- #
# Chaos matrix: poison / deadline / overflow x dynamic / megakernel.
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ("dynamic", "megakernel"))
def test_poison_quarantined_survivors_bit_identical(lm, reqs, scfg,
                                                    baseline, mode):
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg, plan=_plan(mode, guards=True))
    bad = list(reqs)
    bad[1] = Request(prompt=np.full(4, POISON_VALUE, np.int32), max_new=4)
    out = eng.generate(bad, on_fault="quarantine")
    assert eng.last_status[1] == "fault"
    assert out[1].tokens.size == 0
    assert eng.last_retries == 1
    for i in (0, 2, 3, 4):
        assert eng.last_status[i] == "ok"
        assert out[i].tokens.tolist() == baseline[i], i


def test_quarantine_retry_on_a_kept_program(lm, reqs, scfg, baseline):
    """The survivor set's retry runs on the program an earlier call of the
    same request count built, and its tokens equal the fault-free run's."""
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg,
                      plan=ExecutionPlan(mode="dynamic", guards=True))
    rng = np.random.default_rng(8)
    earlier = [Request(prompt=rng.integers(1, cfg.vocab, size=5)
                       .astype(np.int32), max_new=3) for _ in range(4)]
    eng.generate(earlier)                       # builds the program for 4
    bad = list(reqs)
    bad[1] = Request(prompt=np.full(4, POISON_VALUE, np.int32), max_new=4)
    out = eng.generate(bad, on_fault="quarantine")
    assert eng.last_retries == 1
    assert eng.last_program_reused              # the survivors: 4 again
    assert eng.programs_built == 2              # 4, then 5
    assert eng.last_status[1] == "fault"
    for i in (0, 2, 3, 4):
        assert eng.last_status[i] == "ok"
        assert out[i].tokens.tolist() == baseline[i], i


@pytest.mark.parametrize("mode", ("dynamic", "megakernel"))
def test_expired_deadline_sheds_as_timeout(lm, reqs, scfg, baseline, mode):
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg, plan=_plan(mode))
    dls = np.full(len(reqs), 2 ** 30 - 1, np.int32)
    dls[2] = -1                       # expired before the first firing
    out = eng.generate(list(reqs), deadlines=dls)
    assert eng.last_status[2] == "timeout"
    assert out[2].tokens.size == 0    # never admitted, nothing produced
    for i in (0, 1, 3, 4):
        assert eng.last_status[i] == "ok"
        assert out[i].tokens.tolist() == baseline[i], i


@pytest.mark.parametrize("mode", ("dynamic", "megakernel"))
def test_queue_overflow_sheds_excess_requests(lm, reqs, scfg, baseline,
                                              mode):
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg, plan=_plan(mode), queue_depth=0)
    out = eng.generate(list(reqs))     # all 5 arrive at step 0, B=2 slots
    assert eng.last_status == ["ok", "ok", "shed", "shed", "shed"]
    for i in (0, 1):
        assert out[i].tokens.tolist() == baseline[i], i
    for i in (2, 3, 4):
        assert out[i].tokens.size == 0


def test_mid_flight_deadline_keeps_token_prefix(lm, reqs, scfg, baseline):
    """A deadline that expires mid-generation retires the request with
    ``status="timeout"`` and the tokens it produced so far — a strict
    prefix of its fault-free tokens (progress is never un-published)."""
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg)
    dls = np.full(len(reqs), 2 ** 30 - 1, np.int32)
    # The serving clock ticks once per decode step, so deadline 1 admits
    # the request and retires it after its second token (of four).
    dls[0] = 1
    out = eng.generate(list(reqs), deadlines=dls)
    assert eng.last_status[0] == "timeout"
    got = out[0].tokens.tolist()
    assert len(got) < len(baseline[0])
    assert got == baseline[0][:len(got)]
    for i in (1, 2, 3, 4):
        assert out[i].tokens.tolist() == baseline[i], i


def test_injector_validation(lm, reqs, scfg):
    cfg, params = lm
    eng = ActorEngine(cfg, params, scfg)
    wl = eng._workload(reqs, None, None)
    with pytest.raises(ValueError, match="out of range"):
        poison_request(wl, 99)
    with pytest.raises(ValueError, match="not a poison"):
        poison_request(wl, 0, value=3)
    with pytest.raises(ValueError, match="out of range"):
        expire_deadline(wl, -1)
    # pure: the staged workload is untouched
    pw = poison_request(wl, 1)
    assert not np.array_equal(np.asarray(pw.prompts),
                              np.asarray(wl.prompts))
    ew = expire_deadline(wl, 2)
    assert wl.deadlines is None and int(ew.deadlines[2]) == -1


def test_quarantine_needs_guards_and_reraises_unmapped(lm, reqs, scfg):
    cfg, params = lm
    with pytest.raises(ValueError, match="guarded plan"):
        ActorEngine(cfg, params, scfg).generate(list(reqs),
                                                on_fault="quarantine")
    # retries exhausted -> the fault surfaces instead of looping forever
    eng = ActorEngine(cfg, params, scfg,
                      plan=ExecutionPlan(mode="dynamic", guards=True))
    bad = list(reqs)
    bad[0] = Request(prompt=np.full(4, POISON_VALUE, np.int32), max_new=4)
    with pytest.raises(NetworkFaultError):
        eng.generate(bad, on_fault="quarantine", max_retries=0)


# --------------------------------------------------------------------------- #
# Feed-domain validation (satellite: the stream error names chunk AND
# offending request id).
# --------------------------------------------------------------------------- #
def test_stream_feed_domain_error_names_chunk_and_request():
    b = NetworkBuilder()
    b.actor(static_actor("src", (), ("out",),
                         lambda st, ins, rates: (st,
                                                 {"out": jnp.zeros((4, 2,
                                                                    8))})))
    b.actor(static_actor("amp", ("in",), ("out",),
                         map_fire(lambda w: 2.0 * w, "in", "out")))
    b.actor(static_actor("sink", ("in",), (),
                         lambda st, ins, rates: (st, {})))
    b.connect("src.out", "amp.in", rate=4, token_shape=(2, 8), name="f_in",
              domain=(0.0, 100.0), row_id_col=0)
    b.connect("amp.out", "sink.in", rate=4, token_shape=(2, 8),
              name="f_out")
    net = b.build()
    prog = net.compile(ExecutionPlan(mode="dynamic", n_iterations=2,
                                     accelerated=("amp",)))
    feeds = np.ones((6, 4, 2, 8), np.float32)
    feeds[:, :, :, 0] = 7.0            # row id column
    clean = prog.stream({"f_in": feeds})
    np.testing.assert_array_equal(np.asarray(clean["f_out"]), 2 * feeds)
    bad = feeds.copy()
    bad[3, 1, 0, 2] = -5.0             # window 3 -> chunk 1; row id 7
    with pytest.raises(ValueError, match=r"chunk 1.*request id 7"):
        prog.stream({"f_in": bad})
    # NaN is out of every domain, even one with infinite-looking bounds
    nan = feeds.copy()
    nan[0, 0, 1, 3] = np.nan
    with pytest.raises(ValueError, match=r"chunk 0"):
        prog.stream({"f_in": nan})


# --------------------------------------------------------------------------- #
# Kill -> resume: a real SIGKILL mid-run, bit-identical continuation.
# --------------------------------------------------------------------------- #
def _archive_checkpoint(ck: str, tag: str) -> None:
    """Copy a kill-resume snapshot directory to the CI artifact root
    (RESIL_CKPT_ARTIFACT_DIR), so the raw manifests + CRC'd leaves the
    killed child left behind are inspectable after the run."""
    import shutil
    root = os.environ.get("RESIL_CKPT_ARTIFACT_DIR")
    if not root:
        return
    os.makedirs(root, exist_ok=True)
    shutil.copytree(ck, os.path.join(root, tag), dirs_exist_ok=True)


def _run_child(body: str, devices: int = 1, expect_kill: bool = False,
               timeout: int = 600) -> str:
    code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    if expect_kill:
        assert out.returncode == -signal.SIGKILL, (
            f"child exited {out.returncode}, expected SIGKILL\n"
            f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}")
    else:
        assert out.returncode == 0, (
            f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}")
    return out.stdout


_KILL_HOOK = """
import os, signal
import repro.core.program as P
_orig_save = P.save_stream_checkpoint
_n = [0]
def _hooked(*a, **k):
    r = _orig_save(*a, **k)
    _n[0] += 1
    if _n[0] == @KILL_AFTER@:
        os.kill(os.getpid(), signal.SIGKILL)
    return r
P.save_stream_checkpoint = _hooked
"""

_DPD_SETUP = """
import numpy as np, jax.numpy as jnp
from repro.core import ExecutionPlan
from repro.graphs.factories import make_dpd
net, nf = make_dpd(n_firings=8, block_l=64)
accel = tuple(n for n in net.actors if n not in ("source", "sink"))
rng = np.random.default_rng(0)
sig = rng.normal(size=(2, nf * 64)).astype(np.float32)
wins = np.stack([sig[:, i * 64:(i + 1) * 64] for i in range(nf)])[:, None]
feeds = {"f_in": jnp.asarray(wins)}
plan = @PLAN@
prog = net.compile(plan)
"""

_SERVING_SETUP = """
import numpy as np, jax
from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import ActorEngine, Request, ServeConfig
from repro.core import ExecutionPlan
cfg = smoke_config("granite-8b")
params = init_params(jax.random.PRNGKey(0), cfg)
scfg = ServeConfig(batch_size=2, max_prompt=6, max_new=3, eos_id=7)
rng = np.random.default_rng(5)
reqs = [Request(prompt=rng.integers(1, cfg.vocab, size=4).astype(np.int32),
                max_new=3) for _ in range(4)]
net = ActorEngine(cfg, params, scfg).build_network(reqs)
plan = @PLAN@
prog = net.compile(plan)
"""


DPD_STREAM_PLANS = {
    "dynamic": "ExecutionPlan(mode='dynamic', n_iterations=2, "
               "accelerated=accel, trace=True)",
    "megakernel": "ExecutionPlan(mode='megakernel', n_iterations=2, "
                  "accelerated=accel, specialize=False)",
}


@pytest.mark.parametrize("mode", sorted(DPD_STREAM_PLANS))
def test_kill_resume_stream_dpd_bit_identical(tmp_path, mode):
    """Child streams the dpd graph with per-chunk snapshots and is
    SIGKILLed after chunk 2 of 4; a fresh process resumes and its
    outputs, fire counts, sweeps and merged trace are bit-identical to
    an uninterrupted stream."""
    ck = str(tmp_path / "ck")
    setup = _DPD_SETUP.replace("@PLAN@", DPD_STREAM_PLANS[mode])
    _run_child(setup + _KILL_HOOK.replace("@KILL_AFTER@", "2") + f"""
prog.stream(feeds, checkpoint_dir={ck!r}, checkpoint_every=1)
raise SystemExit("stream finished without being killed")
""", expect_kill=True)
    assert any(d.startswith("chunk_") for d in os.listdir(ck))
    _archive_checkpoint(ck, f"stream_dpd_{mode}")
    out = _run_child(setup + f"""
ref = prog.stream(feeds)
ref_fc, ref_sw = prog.last_stream_fire_counts, prog.last_stream_sweeps
ref_tr = prog.last_stream_trace
prog2 = net.compile(plan)
got = prog2.resume_stream({ck!r}, feeds, checkpoint_every=1)
for f in ref:
    np.testing.assert_array_equal(np.asarray(ref[f]), np.asarray(got[f]))
assert prog2.last_stream_fire_counts == ref_fc
assert prog2.last_stream_sweeps == ref_sw
if ref_tr is not None:
    np.testing.assert_array_equal(ref_tr.events,
                                  prog2.last_stream_trace.events)
    assert ref_tr.actor_names == prog2.last_stream_trace.actor_names
print("RESUME_STREAM_OK")
""")
    assert "RESUME_STREAM_OK" in out


SERVING_RUN_PLANS = {
    "dynamic-1dev": ("ExecutionPlan(mode='dynamic')", 1),
    "dynamic-2dev": ("ExecutionPlan(mode='dynamic', devices=2)", 8),
    "megakernel": ("ExecutionPlan(mode='megakernel', specialize=False)", 1),
}


@pytest.mark.parametrize("cell", sorted(SERVING_RUN_PLANS))
def test_kill_resume_run_serving_bit_identical(tmp_path, cell):
    """Child runs the serving graph via run_checkpointed (segments of 5
    sweeps) and is SIGKILLed after the first snapshot; a fresh process
    resumes via resume_run and the final state / fire counts / sweeps
    are bit-identical to an uninterrupted run — including at devices=2,
    where each segment re-enters the sharded runner through the exit-
    merged host state."""
    plan_expr, devices = SERVING_RUN_PLANS[cell]
    ck = str(tmp_path / "ck")
    setup = _SERVING_SETUP.replace("@PLAN@", plan_expr)
    _run_child(setup + _KILL_HOOK.replace("@KILL_AFTER@", "1") + f"""
prog.run_checkpointed({ck!r}, every_sweeps=5)
raise SystemExit("run finished without being killed")
""", devices=devices, expect_kill=True)
    assert any(d.startswith("chunk_") for d in os.listdir(ck))
    _archive_checkpoint(ck, f"run_serving_{cell}")
    out = _run_child(setup + f"""
ref = prog.run()
got = net.compile(plan).resume_run({ck!r})
assert int(got.sweeps) == int(ref.sweeps), (got.sweeps, ref.sweeps)
assert {{k: int(v) for k, v in got.fire_counts.items()}} == \\
    {{k: int(v) for k, v in ref.fire_counts.items()}}
for a, b in zip(jax.tree.leaves(ref.state), jax.tree.leaves(got.state)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("RESUME_RUN_OK")
""", devices=devices)
    assert "RESUME_RUN_OK" in out


def test_kill_resume_run_dpd_devices2(tmp_path):
    """The dpd graph under devices=2: kill after the first segment
    snapshot, resume on a fresh mesh, bit-identical final state."""
    ck = str(tmp_path / "ck")
    setup = """
import numpy as np, jax
from repro.core import ExecutionPlan
from repro.graphs.factories import make_dpd
net, nf = make_dpd(n_firings=6, block_l=64)
plan = ExecutionPlan(mode="dynamic", devices=2)
prog = net.compile(plan)
"""
    _run_child(setup + _KILL_HOOK.replace("@KILL_AFTER@", "1") + f"""
prog.run_checkpointed({ck!r}, every_sweeps=3)
raise SystemExit("run finished without being killed")
""", devices=8, expect_kill=True)
    out = _run_child(setup + f"""
ref = prog.run()
got = net.compile(plan).resume_run({ck!r})
assert int(got.sweeps) == int(ref.sweeps)
for a, b in zip(jax.tree.leaves(ref.state), jax.tree.leaves(got.state)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("RESUME_RUN_OK")
""", devices=8)
    assert "RESUME_RUN_OK" in out


# --------------------------------------------------------------------------- #
# Snapshot integrity: CRC failure falls back to the previous snapshot.
# --------------------------------------------------------------------------- #
def test_torn_snapshot_falls_back_to_previous(tmp_path):
    from repro.checkpoint import (CheckpointIntegrityError,
                                  load_stream_checkpoint,
                                  save_stream_checkpoint)
    d = str(tmp_path / "ck")
    save_stream_checkpoint(d, 1, {"x": np.arange(4)}, {"kind": "t"})
    save_stream_checkpoint(d, 2, {"x": np.arange(8)}, {"kind": "t"})
    # tear the newest snapshot's leaf file (simulated torn write)
    leaf = os.path.join(d, "chunk_00000002", "leaf_0000.npy")
    with open(leaf, "r+b") as f:
        f.seek(0, 2)
        f.truncate(f.tell() - 3)
    payload, meta, step = load_stream_checkpoint(d)
    assert step == 1 and np.asarray(payload["x"]).shape == (4,)
    # with no intact snapshot at all, the failure is loud and typed
    leaf1 = os.path.join(d, "chunk_00000001", "leaf_0000.npy")
    with open(leaf1, "r+b") as f:
        f.write(b"\xff" * 8)
    with pytest.raises(CheckpointIntegrityError):
        load_stream_checkpoint(d)


def test_resume_rejects_mismatched_kind_and_geometry(tmp_path):
    """resume_stream refuses a run snapshot and a geometry drift."""
    import jax.numpy as jnp
    from repro.graphs.factories import make_dpd
    net, nf = make_dpd(n_firings=4, block_l=64)
    plan = ExecutionPlan(mode="dynamic")
    ck = str(tmp_path / "ck")
    prog = net.compile(plan)
    prog.run_checkpointed(ck, every_sweeps=100)
    accel = tuple(n for n in net.actors if n not in ("source", "sink"))
    sprog = net.compile(ExecutionPlan(mode="dynamic", n_iterations=2,
                                      accelerated=accel))
    rng = np.random.default_rng(0)
    sig = rng.normal(size=(2, nf * 64)).astype(np.float32)
    wins = np.stack([sig[:, i * 64:(i + 1) * 64] for i in range(nf)])[:, None]
    with pytest.raises(ValueError, match="resume via"):
        sprog.resume_stream(ck, {"f_in": jnp.asarray(wins)})
    with pytest.raises(ValueError, match="resume via"):
        sprog2 = net.compile(plan)
        sck = str(tmp_path / "sck")
        sprog.stream({"f_in": jnp.asarray(wins)}, checkpoint_dir=sck)
        sprog2.resume_run(sck)


def test_resume_run_of_completed_run_returns_final_result(tmp_path):
    from repro.graphs.factories import make_dpd
    net, _ = make_dpd(n_firings=4, block_l=64)
    plan = ExecutionPlan(mode="dynamic")
    ck = str(tmp_path / "ck")
    ref = net.compile(plan).run()
    got = net.compile(plan).run_checkpointed(ck, every_sweeps=2)
    assert int(got.sweeps) == int(ref.sweeps)
    # the final snapshot is marked done: resume reconstructs the result
    again = net.compile(plan).resume_run(ck)
    assert int(again.sweeps) == int(ref.sweeps)
    for a, b in zip(jax.tree.leaves(ref.state), jax.tree.leaves(again.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
