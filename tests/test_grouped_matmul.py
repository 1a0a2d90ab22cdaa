"""``ops/grouped_matmul.py`` interpreted on the CPU against a loop over the
groups: the rows' products (plain and with the matrix transposed) and the
weights' gradients summed into their slabs, for group sizes that leave a
group empty, share a row tile between groups, end before the rows' buffer
does, and fill it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from determined_clone_tpu.ops import grouped_matmul as gm

# (rows, contraction, columns, sizes, tiles)
CASES = {
    "a_group_is_empty_and_rows_are_left_over":
        (64, 32, 48, [10, 0, 30, 7], (16, 128, 128)),
    "one_group_holds_every_row": (64, 32, 48, [0, 0, 64, 0], (16, 128, 128)),
    "groups_end_on_tile_edges_and_the_sides_are_tiled":
        (64, 256, 384, [16, 16, 16, 16], (16, 128, 128)),
    "three_groups_share_one_tile": (128, 256, 384, [1, 100, 3],
                                    (32, 128, 256)),
    "one_tile_holds_all_and_most_of_it_is_nobodys":
        (64, 32, 48, [5, 0, 0, 0], (64, 128, 128)),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_grouped_products_are_the_loop_over_groups(case, dtype):
    """fp32 operands: the sums come in another order (1e-5 of the largest
    entry); bfloat16 operands are multiplied exactly and summed in fp32 on
    both sides, so the same bound holds. Rows of no group are not compared:
    ``grouped_matmul`` leaves them unwritten. A group without rows keeps
    its slab bit for bit."""
    m, k, n, sizes, tiles = CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(m + k + n), 4)
    rows = jax.random.normal(keys[0], (m, k)).astype(dtype)
    stack = jax.random.normal(keys[1], (len(sizes), k, n)).astype(dtype)
    rhs = jax.random.normal(keys[2], (m, n)).astype(dtype)
    into = jax.random.normal(keys[3], (len(sizes), k, n))
    ends = np.cumsum(sizes)
    f32 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        out = gm.grouped_matmul(rows, stack, jnp.array(sizes), tiles=tiles)
        out_t = gm.grouped_matmul(rhs, stack, jnp.array(sizes),
                                  transpose=True, tiles=tiles)
        slabs = gm.grouped_outer(rows.T, rhs, jnp.array(sizes), into,
                                 tiles=tiles)
    for g, (size, end) in enumerate(zip(sizes, ends)):
        mine = slice(end - size, end)
        for got, want in (
                (out[mine], f32(rows)[mine] @ f32(stack)[g]),
                (out_t[mine], f32(rhs)[mine] @ f32(stack)[g].T),
                (slabs[g], f32(into)[g] + f32(rows)[mine].T @ f32(rhs)[mine])):
            np.testing.assert_allclose(got, want, atol=1e-5 * max(
                1.0, float(np.max(np.abs(want)))) if size else 0.0)


def test_rows_that_are_not_whole_tiles_are_refused():
    with pytest.raises(ValueError, match="whole tiles"):
        gm.grouped_matmul(jnp.zeros((48, 8)), jnp.zeros((2, 8, 8)),
                          jnp.array([3, 4]), tiles=(32, 128, 128))


def test_tiles_divide_the_sides():
    assert gm._fit(512, 2048) == 512 and gm._fit(2048, 1536) == 1536
    assert gm._fit(1024, 1536) == 768 and gm._fit(128, 48) == 48
    # no whole 128-lane divisor: the side is taken whole
    assert gm._fit(128, 200) == 200


@pytest.mark.parametrize("rows_a_step", [16, 64])
def test_add_rows_adds_every_groups_rows_to_their_own(rows_a_step):
    """Rows of ``y`` that one group, two groups or nobody adds to; the rows
    past the last group carry an index outside ``y`` and are never read. A
    group's indices differ, as a token's pairs with one expert do; the sums
    are the loop's to the last bit (one add a pair, in the pairs' order)."""
    n, m, sizes = 40, 64, [10, 0, 30, 7]
    rng = np.random.default_rng(0)
    index = np.concatenate(
        [np.sort(rng.choice(n, s, replace=False)) for s in sizes]
        + [np.full(m - sum(sizes), 12345)]).astype(np.int32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (m, 8, 128))
    y = jax.random.normal(jax.random.PRNGKey(1), (n, 8, 128))
    want = np.array(y)
    for r in range(sum(sizes)):
        want[index[r]] += np.asarray(rows)[r]
    got = gm.add_rows(y + 0, jnp.array(index), rows, jnp.array(sizes),
                      rows_a_step=rows_a_step)
    np.testing.assert_array_equal(got, want)
    assert len(set(index[:sum(sizes)].tolist())) < sum(sizes)  # shared rows
