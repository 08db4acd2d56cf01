"""The closed-loop fleet's inputs come from the seed alone: the same seed
gives the same instructions, frames and start order; every seed gives the
same sizes and start times, in another order;
no two steps share a frame."""
import numpy as np

from harness import spec
from harness.fleet import Fleet

MIX = spec.traffic("robots32")
SHAPE = (576, 1024)


def requests(seed, n=40):
    f = Fleet(MIX, SHAPE, 152064, seed)
    return f, [f.frame() for _ in range(n)]


def test_same_seed_same_requests():
    big = 2**31 + 12345
    a, fa = requests(big)
    b, fb = requests(big)
    assert np.array_equal(a.instructions, b.instructions)
    assert np.array_equal(a.start_s, b.start_s)
    assert all(np.array_equal(x, y) for x, y in zip(fa, fb))


def test_seeds_change_order_not_sizes():
    a, fa = requests(1)
    b, fb = requests(2)
    assert a.instructions.shape == b.instructions.shape == (32, 64)
    assert not np.array_equal(a.instructions, b.instructions)
    assert np.array_equal(np.sort(a.start_s), np.sort(b.start_s))
    assert not np.array_equal(a.start_s, b.start_s)
    assert fa[0].shape == SHAPE and fa[0].dtype == fb[0].dtype
    assert a.new_tokens == b.new_tokens == 144 + 48
    assert int(a.instructions.max()) < 152064


def test_every_step_has_its_own_frame():
    _, frames = requests(3, n=200)
    keys = {f.tobytes() for f in frames}
    assert len(keys) == len(frames)
