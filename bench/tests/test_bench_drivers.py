"""Every driver and every graph adapter brings its own pieces, so that a
new configuration is added as new files; the smoke sizes that moved into
them are those the committed cells always had."""
import copy
import glob
import os

import pytest

from bench import harness
from bench.tests import smoke


def modules(kind):
    return sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(harness.BENCH, kind, "*.py")))


@pytest.mark.parametrize("name", modules("drivers"))
def test_driver_brings_its_pieces(name):
    mod = harness.load_module("drivers", name)
    assert callable(mod.Session) and callable(mod.smoke)
    assert callable(mod.corrupt) and isinstance(mod.CONTROL, dict)


@pytest.mark.parametrize("name", modules("graphs"))
def test_graph_adapter_brings_its_pieces(name):
    mod = harness.load_module("graphs", name)
    assert isinstance(mod.FEED, str) and isinstance(mod.FETCH, str)
    assert callable(mod.Graph) and callable(mod.smoke)


def _committed(workload):
    full = harness.resolve(harness.load_spec(), workload)
    return smoke._config(workload), copy.deepcopy(full.traffic)


def _dpd_before(c, mix):
    c.update(block_l=1024, reconf_period_samples=2048)
    c["plan"] = dict(c["plan"], n_iterations=2)
    mix.update(windows_per_call=8, signal_pool=3)


def _lm_before(c, mix):
    c.update(num_hidden_layers=24, hidden_size=64, intermediate_size=128,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=503, initializer_range=0.155)
    c["serve"] = dict(c["serve"], batch_size=4, max_prompt=16, max_new=8)
    mix.update(requests_per_call=6,
               prompt_len=dict(median=16, sigma=0.0, lo=16, hi=16),
               budget=dict(median=4, sigma=0.7, lo=2, hi=8))


@pytest.mark.parametrize("workload,before", [
    ("dpd.reconf", _dpd_before), ("dpd.allon", _dpd_before),
    ("danube.fullprompt", _lm_before)])
def test_smoke_cell_as_before(workload, before):
    c, mix = _committed(workload)
    before(c, mix)
    cell = smoke.cell(workload)
    assert cell.config == c and cell.traffic == mix


def test_smoke_leaves_the_committed_files_alone():
    c, mix = _committed("md.qvga")
    smoke.driver(smoke.cell("md.qvga")).smoke(c, mix)
    assert (c, mix) == _committed("md.qvga")
