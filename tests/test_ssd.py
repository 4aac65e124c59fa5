"""The chunked state-space dual kernel (``ops/ssd.py``) against the plain
recurrence, on the CPU in interpret mode.

THE CARRY.  The benchmark's checkpoints draw ``A_log`` and ``dt_bias`` N(0,
std): a state that halves every token, under which a kernel that lost its state
between chunks would still read right (PERF.md, question 23).  Here the rates
are the PUBLISHED initialisation (``A_log`` = log(1..heads), ``dt_bias`` the
inverse softplus of steps drawn log-uniformly in [0.001, 0.1]): a state lives
hundreds of positions, several chunks, so a wrong carry fails.

Tolerances.  Kernel and recurrence are both float32 here and differ in the
order of their sums and in exp(a) exp(b) against exp(a + b): outputs of size 1
to 10 agree to 2e-4 absolute (they read 1e-6 to 2e-5), states likewise.  In
bfloat16 the kernel's products round their operands (2^-9 each): 5e-2 of
outputs of size 1 to 10.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from llm_weighted_consensus_tpu.ops import ssd  # noqa: E402

TOL = 2e-4


def long_memory(b, s, heads, p, groups, n, seed=0, dtype=jnp.float32):
    """Inputs under the published initialisation: (xs, dt, a, b, c, d)."""
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    a = -np.arange(1, heads + 1, dtype=np.float32)  # -exp(A_log), A_log = log(1..heads)
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=heads)).astype(np.float32)
    dt_bias = step + np.log(-np.expm1(-step))  # the inverse softplus
    dt = jax.nn.softplus(jnp.asarray(0.3 * normal(b, s, heads) + dt_bias))
    d = 1.0 + 0.1 * normal(heads)
    cast = lambda x: jnp.asarray(x).astype(dtype)  # noqa: E731
    return (
        cast(normal(b, s, heads * p)), dt, jnp.asarray(a), cast(normal(b, s, groups * n)),
        cast(normal(b, s, groups * n)), jnp.asarray(d),
    )


def both(args, lens, groups, **kernel):
    lens = jnp.asarray(lens, jnp.int32)
    got = ssd.ssd_chunked(*args, lens, groups=groups, **kernel)
    want = ssd.ssd_recurrent(*args, lens, groups=groups)
    live = (np.arange(args[0].shape[1])[None, :] < np.asarray(lens)[:, None])[..., None]
    return got, want, live


@pytest.mark.parametrize(
    "s, chunk, lens",
    [
        (64, 8, [64, 64]),  # eight whole chunks
        (64, 8, [37, 53]),  # ``lens`` inside a chunk
        (64, 8, [32, 8]),  # ``lens`` at a chunk's edge
        (64, 8, [64, 0]),  # a call that is all padding: its state stays zero
        (75, 16, [75, 41]),  # a length that is no whole chunk
        (40, 128, [40, 17]),  # one chunk, the served chunk length
    ],
    ids=["whole", "inside", "edge", "empty", "ragged", "one-chunk"],
)
def test_the_kernel_is_the_recurrence_with_a_long_memory(s, chunk, lens):
    args = long_memory(2, s, heads=6, p=8, groups=2, n=16)
    (y, state), (y_want, state_want), live = both(args, lens, 2, chunk=chunk, heads_per_step=3)
    assert np.abs(np.where(live, y - y_want, 0)).max() < TOL
    assert np.abs(state - state_want).max() < TOL
    # the memory is long: the state still holds the first chunk (a decay of
    # exp(-sum dt) over the call, far from 0 for the slow heads)
    if min(lens) > 16:
        assert float(jnp.abs(state_want).max()) > 0.1


def test_a_kernel_that_lost_its_state_between_chunks_would_read_wrong():
    """What the test above would miss with a state that halves every token:
    here the second chunk's output WITHOUT the first chunk's state is far off."""
    args = long_memory(1, 32, heads=4, p=8, groups=2, n=16, seed=1)
    lens = jnp.asarray([32], jnp.int32)
    y, _ = ssd.ssd_chunked(*args, lens, groups=2, chunk=16)
    second = tuple(x[:, 16:] if x.ndim == 3 else x for x in args)
    y_cold, _ = ssd.ssd_recurrent(*second, jnp.asarray([16], jnp.int32), groups=2)
    assert np.abs(y[:, 16:] - y_cold).max() > 100 * TOL


@pytest.mark.parametrize("heads_per_step", [1, 2, 3, 6, 16])
def test_every_head_reads_its_own_group_s_b_and_c(heads_per_step):
    """Head j reads group j // (heads / groups): with three heads a group a
    kernel that read group j % 2 would fail at heads 1, 2, 3 and 4, whatever
    the heads of a grid step."""
    heads, groups, n = 6, 2, 16
    args = long_memory(1, 24, heads, p=8, groups=groups, n=n, seed=2)
    (y, state), (y_want, state_want), _ = both(
        args, [24], groups, chunk=8, heads_per_step=heads_per_step
    )
    assert np.abs(y - y_want).max() < TOL and np.abs(state - state_want).max() < TOL
    # the twin itself, against the groups written out: head j's state from group j // 3
    xs, dt, a, b, c, d = args
    j = 4
    one = ssd.ssd_recurrent(
        xs[..., j * 8:(j + 1) * 8], dt[..., j:j + 1], a[j:j + 1], b[..., n:], c[..., n:],
        d[j:j + 1], jnp.asarray([24], jnp.int32), groups=1,
    )
    assert np.abs(one[0] - y_want[..., j * 8:(j + 1) * 8]).max() < 1e-6
    wrong = ssd.ssd_recurrent(
        xs[..., j * 8:(j + 1) * 8], dt[..., j:j + 1], a[j:j + 1], b[..., :n], c[..., :n],
        d[j:j + 1], jnp.asarray([24], jnp.int32), groups=1,
    )
    assert np.abs(wrong[0] - y_want[..., j * 8:(j + 1) * 8]).max() > 100 * TOL


def test_padding_behind_lens_leaves_the_state_and_a_decoded_token_goes_on_from_it():
    """The state after ``lens - 1`` is what the recurrence over the first
    ``lens`` positions alone leaves, whatever stands in the padded slots; one
    ``ssd_step`` from it is position ``lens`` of the longer scan."""
    args = long_memory(2, 48, heads=4, p=8, groups=2, n=16, seed=3)
    lens = np.array([29, 16], np.int32)
    _, state = ssd.ssd_chunked(*args, jnp.asarray(lens), groups=2, chunk=16)
    noisy = (args[0].at[0, 29:].set(1e3), args[1].at[0, 29:].set(5.0), *args[2:])
    _, again = ssd.ssd_chunked(*noisy, jnp.asarray(lens), groups=2, chunk=16)
    assert np.array_equal(np.asarray(state), np.asarray(again))
    xs, dt, a, b, c, d = args
    at = lambda v: jnp.stack([v[row, n] for row, n in enumerate(lens)])  # noqa: E731
    y, stepped = ssd.ssd_step(state, at(xs), at(dt), a, at(b), at(c), d)
    y_want, state_want = ssd.ssd_recurrent(*args, jnp.asarray(lens + 1), groups=2)
    assert np.abs(stepped - state_want).max() < TOL
    for row, n in enumerate(lens):
        assert np.abs(y[row] - y_want[row, n]).max() < TOL


@pytest.mark.parametrize("s, lens", [(128, [128]), (75, [37])])
def test_the_kernel_in_bfloat16_keeps_its_state_in_float32(s, lens):
    args = long_memory(1, s, heads=4, p=8, groups=2, n=16, seed=4, dtype=jnp.bfloat16)
    (y, state), (y_want, state_want), live = both(args, lens, 2, chunk=16)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert np.abs(np.where(live, y.astype(jnp.float32) - y_want.astype(jnp.float32), 0)).max() < 5e-2
    assert np.abs(state - state_want).max() < 5e-2


def test_shapes_the_kernel_cannot_serve_are_refused():
    args = long_memory(1, 16, heads=6, p=8, groups=2, n=16)
    lens = jnp.asarray([16], jnp.int32)
    with pytest.raises(ValueError, match="6 heads of 8 on 4 groups"):
        ssd.ssd_chunked(*args, lens, groups=4)
    # compiled for the chip a head is whole 128-lane columns and so are the states
    with pytest.raises(ValueError, match="heads of 8"):
        ssd.ssd_chunked(*args, lens, groups=2, interpret=False)
    assert ssd._heads_a_step(16, 16) == 16 and ssd._heads_a_step(3, 16) == 3
    assert ssd._heads_a_step(6, 4) == 3 and ssd._heads_a_step(16, 8) == 8
