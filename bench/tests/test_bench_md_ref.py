"""The benchmark's motion-detection reference (``bench.lib.md_ref``) is the
program's oracle exactly, on seeded small clips of the benchmark's own
content and of uniform noise, and its bfloat16 control is not."""
import ml_dtypes
import numpy as np
import pytest

from bench.lib import md_ref, traffic


def clip(seed: int, kind: str) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "noise":
        return rng.uniform(0, 255, (8, 48, 64)).astype(np.float32)
    return traffic.md_clip(rng, traffic.load("qvga"), 12, 48, 64)


@pytest.mark.parametrize("kind", ["clip", "noise"])
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_reference_is_the_program_oracle(seed, kind):
    from repro.graphs.motion_detection import md_oracle
    video = clip(seed, kind)
    assert np.array_equal(md_ref.md_oracle(video), md_oracle(video))


def test_bfloat16_gauss_sums_differ():
    video = clip(3, "noise")
    assert np.count_nonzero(md_ref.md_oracle(video) != md_ref.md_oracle(
        video, dtype=ml_dtypes.bfloat16)) > 0


def test_clip_is_seeded_and_mostly_still():
    mix = traffic.load("qvga")
    a, b = (traffic.md_clip(np.random.default_rng(7), mix, 12, 48, 64)
            for _ in range(2))
    assert a.dtype == np.uint8 and a.shape == (12, 48, 64)
    assert np.array_equal(a, b)
    moving = md_ref.md_oracle(a)[1:] > 0
    assert 0 < moving.mean() < 0.25
