"""The operation and byte counts that rooflines and utilisations divide by,
against counts made by hand."""
import numpy as np
import pytest

from bench.lib import work


def test_fir_branch_counts():
    # 17 input samples (8 + 9 history): |x|^2 (3) and x * scale (2) each;
    # 8 flops per tap per output sample; f32 planes in, taps, planes out.
    assert work.fir_branch(8, 1) == (5 * 17 + 8 * 10 * 8,
                                     4 * (2 * 17 + 2 * 10 + 2 * 8))
    assert work.fir_branch(8, 3)[0] == 7 * 17 + 640


def test_dpd_window_and_call():
    w = 2 * 8 * 4
    assert work.dpd_window(8, 0) == (0, 4 * w)
    f1, b1 = work.fir_branch(8, 1)
    f2, b2 = work.fir_branch(8, 2)
    assert work.dpd_window(8, 2) == (f1 + f2 + 2 * 8, 4 * w + b1 + b2 + 2 * w)
    sched = np.array([2, 0, 1])
    assert work.dpd_call(8, sched) == tuple(
        sum(x) for x in zip(*(work.dpd_window(8, n) for n in sched)))
    assert work.fir_call(8, sched) == (2 * f1 + f2, 2 * b1 + b2)


def test_lm_request_flops():
    c = dict(hidden_size=4, intermediate_size=6, num_attention_heads=2,
             num_key_value_heads=1, head_dim=2, num_hidden_layers=3,
             vocab_size=10)
    per_layer = 4 * 4 + 2 * 4 * 2 + 4 * 4 + 3 * 4 * 6
    per_token = 2 * (3 * per_layer + 4 * 10)
    assert work.lm_matmul_flops_per_token(c) == per_token
    # 5 prompt tokens and 3 generated: forwards at 7 positions, attending
    # to 1 + 2 + ... + 7 positions, 4 * heads * head_dim * layers each.
    assert work.lm_request_flops(c, 5, 3) == 7 * per_token + 28 * 4 * 2 * 2 * 3


def test_unknown_device_is_an_error():
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("cpu")
