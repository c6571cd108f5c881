"""``models/_live_rows.live_rows`` alone: row-wise work over the blocks that
hold the live rows, under a trip count that is traced."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.models._live_rows import live_rows, row_block, rows_run

S, BLOCK = 32, 8


def _fn(x, heads_first, pos):
    """Three inputs (rows on axis 0, 1, 0), two outputs (rows on axis 0 and
    1), each row of its own inputs alone."""
    y = jnp.tanh(x) * pos[:, None] + heads_first.sum(axis=0)
    return {"rows": y @ jnp.ones((x.shape[1], 3), x.dtype),
            "heads": jnp.swapaxes(y, 0, 1)[None] * 2.0}


def _inputs():
    rs = np.random.RandomState(0)
    return (jnp.asarray(rs.randn(S, 5), jnp.float32),
            jnp.asarray(rs.randn(2, S, 5), jnp.float32),
            jnp.arange(S, dtype=jnp.float32))


AXES = dict(in_axes=(0, 1, 0), out_axes=(2, 0))     # leaves: heads, rows


@pytest.mark.parametrize("live", [1, BLOCK - 1, BLOCK, BLOCK + 1,
                                  2 * BLOCK, S - 1, S])
def test_live_blocks_equal_the_plain_call_and_dead_blocks_are_zero(live):
    arrays = _inputs()
    want = _fn(*arrays)
    got = jax.jit(lambda n, *a: live_rows(_fn, a, n, BLOCK, **AXES))(
        jnp.int32(live), *arrays)
    ran = -(-live // BLOCK) * BLOCK
    assert ran == rows_run(S, live, BLOCK)
    np.testing.assert_allclose(got["rows"][:ran], want["rows"][:ran],
                               atol=1e-6)
    np.testing.assert_allclose(got["heads"][..., :ran],
                               want["heads"][..., :ran], atol=1e-6)
    assert not np.asarray(got["rows"][ran:]).any()
    assert not np.asarray(got["heads"][..., ran:]).any()


def test_a_dead_block_is_never_read():
    """NaN in the rows of the blocks past the live ones reaches no output:
    the body did not run there."""
    x, h, pos = _inputs()
    x = x.at[2 * BLOCK:].set(jnp.nan)
    got = live_rows(_fn, (x, h, pos), jnp.int32(BLOCK + 3), BLOCK, **AXES)
    assert all(bool(jnp.isfinite(v).all()) for v in got.values())


def test_without_an_extent_it_is_the_plain_call():
    arrays = _inputs()
    want = _fn(*arrays)
    for got in (live_rows(_fn, arrays, None, BLOCK, **AXES),
                live_rows(_fn, arrays, jnp.int32(3), S, **AXES)):
        for k in want:          # a block as wide as the bucket: no loop
            np.testing.assert_array_equal(got[k], want[k])
    text = jax.jit(lambda *a: live_rows(_fn, a, None, BLOCK, **AXES)).lower(
        *arrays).as_text()
    assert "while" not in text


def test_the_trip_count_is_traced():
    """The lowered text holds a ``while`` whose bound is an argument of the
    program, not a constant: one program serves every prompt length."""
    arrays = _inputs()
    text = jax.jit(lambda n, *a: live_rows(_fn, a, n, BLOCK, **AXES)).lower(
        jnp.int32(9), *arrays).as_text()
    assert text.count("stablehlo.while") == 1
    head = text[text.index("stablehlo.while"):]
    cond = head[:head.index("} do {")]
    bound = re.search(r"stablehlo\.compare\s+LT,\s+%\w+,\s+(%\w+)",
                      cond).group(1)
    # the loop's bound enters it as a value made from the program's first
    # argument (``live``), and no constant
    start = re.search(rf"{re.escape(bound)} = (%\w+)", head).group(1)
    made = re.search(rf"{re.escape(start)} = (.*)", text).group(1)
    assert "constant" not in made and "%arg0" in text[:text.index(made)]


def test_a_block_that_does_not_divide_the_bucket_is_refused():
    with pytest.raises(ValueError, match="does not divide"):
        live_rows(_fn, _inputs(), jnp.int32(3), 5, **AXES)


@pytest.mark.parametrize("rows,want,block", [
    (16384, 1024, 1024), (2048, 1024, 1024), (512, 1024, 512),
    (17408, 2048, 1024), (17408, 1024, 1024), (17408, 512, 512)])
def test_row_block_divides_every_bucket(rows, want, block):
    assert row_block(rows, want) == block
    assert rows_run(rows, rows, want) == rows
    assert rows_run(rows, 1, want) == block
    if rows > block:
        assert rows_run(rows, block + 1, want) == 2 * block
