"""Paper application graphs: Motion Detection (§4.1) + DPD (§4.2)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecutionPlan, RuntimeMode, assert_mode_allows
from repro.graphs.dpd import build_dpd, dpd_oracle
from repro.graphs.motion_detection import build_motion_detection, md_oracle


@pytest.mark.parametrize("rate", [1, 4])
def test_motion_detection_matches_oracle(rng, rate):
    NF, H, W = 8, 48, 64
    video = rng.uniform(0, 255, (NF, H, W)).astype(np.float32)
    net = build_motion_detection(NF, rate=rate, frame_hw=(H, W),
                                 video=jnp.asarray(video))
    prog = net.compile(mode="static", n_iterations=NF // rate)
    prog.run()
    np.testing.assert_allclose(np.asarray(prog.collect("sink")),
                               md_oracle(video))


def test_motion_detection_buffer_memory_table1():
    """Eq. 1 totals reproduce paper Table 1 (3.46 MB heterog config)."""
    assert abs(build_motion_detection(8, rate=4).buffer_bytes() / 1e6 - 3.456) < 1e-3
    assert abs(build_motion_detection(8, rate=1).buffer_bytes() / 1e6 - 0.922) < 1e-3


def test_dpd_buffer_memory_table1():
    assert abs(build_dpd(4).buffer_bytes() / 1e6 - 11.53) < 0.1  # paper: 11.5


def test_dpd_dynamic_rates_match_oracle(rng):
    NF, L = 4, 1024
    sig = rng.normal(size=(2, NF * L)).astype(np.float32)
    sched = np.array([2, 2, 10, 5], np.int32)
    net = build_dpd(NF, active_schedule=sched, block_l=L,
                    signal=jnp.asarray(sig))
    prog = net.compile(mode="static", n_iterations=NF)
    prog.run()
    got = np.asarray(prog.collect("sink"))
    np.testing.assert_allclose(got, dpd_oracle(sig, sched, L),
                               rtol=6e-4, atol=6e-4)
    # token-driven scheduler agrees
    dyn = net.compile(ExecutionPlan(mode="dynamic"))
    result = dyn.run()
    np.testing.assert_allclose(np.asarray(dyn.collect("sink")),
                               dpd_oracle(sig, sched, L), rtol=6e-4, atol=6e-4)
    assert int(result.fire_counts["config"]) == NF


def test_dpd_static_variant_is_dal_compatible(rng):
    """The all-active rewrite runs under STATIC_DAL; the dynamic graph is
    rejected — reproducing the paper's 'n/a' cells in Table 4."""
    NF, L = 2, 512
    sig = rng.normal(size=(2, NF * L)).astype(np.float32)
    dyn = build_dpd(NF, block_l=L, signal=jnp.asarray(sig))
    with pytest.raises(ValueError, match="STATIC_DAL"):
        assert_mode_allows(dyn, RuntimeMode.STATIC_DAL)
    static = build_dpd(NF, block_l=L, signal=jnp.asarray(sig),
                       static_all_active=True)
    assert_mode_allows(static, RuntimeMode.STATIC_DAL)
    static.compile(mode="static", n_iterations=NF,
                   runtime_mode=RuntimeMode.STATIC_DAL).run()


def test_lm_pipeline_stage_network_matches_reference():
    """The fourth paper graph on the unified surface: LM pipeline stages as
    a builder-constructed actor network, executed by Program, == the
    sequential stage oracle."""
    from repro.configs import smoke_config
    from repro.graphs.lm_pipeline import (build_lm_stage_network,
                                          lm_stage_network_forward,
                                          pipeline_forward_reference)
    from repro.models.lm import init_params
    cfg = smoke_config("granite-8b")
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab)
    got = lm_stage_network_forward(params, cfg, tokens, n_stages=2)
    want = pipeline_forward_reference(params, cfg, tokens, n_stages=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)
    # The network also streams: stages accelerated, activations fed/fetched
    # chunk-by-chunk through the boundary channels.
    net = build_lm_stage_network(params, cfg, tokens, n_stages=2)
    prog = net.compile(mode="static", n_iterations=2,
                       accelerated=("stage0", "stage1"))
    x = net.actors["source"].init()[0]               # staged activations
    outs = prog.stream({"f_s0": np.asarray(x)[:, None]})
    full = net.compile(mode="static", n_iterations=4)
    want_y = np.asarray(full.collect("sink", full.run().state))
    np.testing.assert_allclose(np.asarray(outs["f_out"])[:, 0], want_y,
                               rtol=1e-5, atol=1e-5)


def test_dpd_static_equals_dynamic_all_active(rng):
    """With every branch enabled the dynamic and static graphs agree."""
    NF, L = 3, 512
    sig = rng.normal(size=(2, NF * L)).astype(np.float32)
    sched = np.full(NF, 10, np.int32)
    dyn = build_dpd(NF, active_schedule=sched, block_l=L, signal=jnp.asarray(sig))
    sta = build_dpd(NF, block_l=L, signal=jnp.asarray(sig), static_all_active=True)
    pd = dyn.compile(mode="static", n_iterations=NF)
    ps = sta.compile(mode="static", n_iterations=NF)
    a = np.asarray(pd.collect("sink", pd.run().state))
    b = np.asarray(ps.collect("sink", ps.run().state))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
