"""The benchmark's memory is one state plus one block of examples, whatever
the model's size, and it judges a federation of one client: the reference
round takes its state donated and steps ``reference_example_block``
examples at a time, the run's before-states live on the host, and Phase 1
accepts M = 1."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench import reference as ref
from chipbench_util import cell_names, shrink

SEED = 2 ** 33 + 41


def _round_memory(b, *, M=2, F=4096, C=64, n=32):
    """The compiled co-train round's memory analysis on this backend at a
    linear size where one example's gradient is 1 MB, with its state's
    bytes."""
    cell = harness.load_cell("linear-c10.full")
    cfg = dict(cell["cfg"], feat_dim=F, num_classes=C,
               reference_example_block=b)
    hp = harness.reference_hp(cfg, dict(cell["mix"], local_steps=1))
    S = jax.ShapeDtypeStruct
    model = {k: S((M,) + v, jnp.float32)
             for k, v in cell["kind"].param_shapes(cfg).items()}
    state = {"private": model, "proxy": dict(model)}
    compiled = ref._round.lower(
        cell["kind"], ref._static(cfg), tuple(sorted(hp.items())),
        (("kind", "full"),), n, 1, None, state,
        S((M, n + 8, F), jnp.float32), S((M, n + 8), jnp.int32),
        S((2,), jnp.uint32), S((), jnp.int32), S((), jnp.float32),
        S((M,), jnp.int32), S((1,), jnp.float32)).compile()
    state_bytes = sum(4 * np.prod(s.shape)
                      for s in jax.tree_util.tree_leaves(state))
    return compiled.memory_analysis(), state_bytes, F * C + C


def test_reference_temporaries_scale_with_the_example_block():
    """Blocks of 4 of a batch of 32 hold 28 fewer per-example gradients
    than the whole batch in one block, and the state is donated: its bytes
    come back aliased as the output."""
    whole, state_bytes, D = _round_memory(32)
    blocks, _, _ = _round_memory(4)
    assert whole.temp_size_in_bytes - blocks.temp_size_in_bytes \
        >= 0.8 * 28 * D * 4
    for ma in (whole, blocks):
        assert ma.alias_size_in_bytes >= state_bytes


def test_example_blocks_follow_the_unblocked_reference():
    """Blocks of 4 examples read ``correct: true`` in a whole run, and
    their reference numbers agree with the batch in one block. Each sum
    over the batch is then taken in another order (within each block, then
    over the blocks in float32), so the two differ by float32 rounding,
    carried through four bootstrap rounds and one chunk of SGD: a few units
    in the last place of each sum, far under 1e-5 relative (about 80
    float32 ulps). Test predictions are counted alike."""
    cell = shrink(harness.load_cell("linear-c10.full"))
    keys = harness.run_keys(SEED)
    data = cell["kind"].make_data(cell["mix"], cell["cfg"], keys["data"],
                                  SEED)
    one = harness.reference_outputs(cell, data, keys, dtype=jnp.float32,
                                    fault=None, seed=SEED)
    cell["cfg"]["reference_example_block"] = 4
    blocks = harness.reference_outputs(cell, data, keys, dtype=jnp.float32,
                                       fault=None, seed=SEED)
    assert blocks["groups"] == one["groups"]
    for k in ("boot_change", "change"):
        for leaf in one[k]:
            assert blocks[k][leaf] == pytest.approx(one[k][leaf], rel=1e-5)
    for k in ("dist", "losses"):
        np.testing.assert_allclose(blocks[k], one[k], rtol=1e-5)
    np.testing.assert_array_equal(blocks["correct"], one["correct"])
    out = harness.run(cell, SEED, 0.1, False, t0=0.0, require_chip=False,
                      kernels={"backend": "ref"}, log=lambda m: None)
    assert out["correct"] is True


def test_an_example_block_must_divide_the_batch():
    cell = shrink(harness.load_cell("linear-c10.full"))
    cell["cfg"]["reference_example_block"] = 5
    keys = harness.run_keys(SEED)
    data = cell["kind"].make_data(cell["mix"], cell["cfg"], keys["data"],
                                  SEED)
    with pytest.raises(ValueError, match="does not divide"):
        harness.reference_outputs(cell, data, keys, dtype=jnp.float32,
                                  fault=None, seed=SEED)


def test_first_chunk_keeps_one_state_on_the_device():
    """After ``first_chunk`` the only device arrays as large as a state
    leaf that it made are the engine's state and the data; the states the
    changes were read from are host arrays."""
    cell = shrink(harness.load_cell("linear-c10.full"))
    keys = harness.run_keys(SEED)
    data = cell["kind"].make_data(cell["mix"], cell["cfg"], keys["data"],
                                  SEED)
    before = {id(a) for a in jax.live_arrays()}
    _, fd, state, run_out, _, _ = harness.first_chunk(
        cell, SEED, keys, data, kernels={"backend": "ref"}, keep=True)
    leaf = min(t.nbytes for t in jax.tree_util.tree_leaves(state)
               if t.ndim > 2)
    allowed = {id(a) for a in jax.tree_util.tree_leaves((state, fd, data))}
    big = [a.shape for a in jax.live_arrays()
           if a.nbytes >= leaf and id(a) not in allowed | before]
    assert big == []
    for tree in run_out["states"]:
        assert all(isinstance(t, np.ndarray)
                   for t in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("cell_name", cell_names())
def test_one_client_is_a_federation(cell_name):
    """The program's chunk cache is keyed without the number of clients,
    and a cached chunk holds the strategy of the run that built it, so a
    process that ran 16 clients clears it before it runs one."""
    from repro.engine.loop import clear_chunk_cache
    cell = shrink(harness.load_cell(cell_name))
    cell["mix"]["clients"] = 1
    clear_chunk_cache()
    try:
        out = harness.run(cell, SEED, 0.1, False, t0=0.0, require_chip=False,
                          kernels={"backend": "ref"}, log=lambda m: None)
    finally:
        clear_chunk_cache()
    assert out["correct"] is True
    assert out["checks"]["group_gap"]["value"] == 0.0
    assert out["checks"]["dist_gap"]["value"] == 0.0


def test_greedy_groups_of_one_client():
    assert ref.greedy_groups(np.zeros((1, 1)), 8, 35, 3) == [[0]]


def test_greedy_groups_draw_as_before_from_two_clients_up():
    """M = 2..16 on fixed seeds, with all peers and with 3 sampled peers:
    the groups of the procedure as it stood before it accepted M = 1."""
    h = hashlib.sha256()
    for M in range(2, 17):
        for seed in (0, 7, 2 ** 33 + 5):
            rng = np.random.default_rng(1000 * M + seed % 1000)
            w = rng.normal(size=(M, 6))
            dist = np.abs(w[:, None] - w[None]).sum(-1)
            h.update(repr(ref.greedy_groups(dist, 4, 35 if seed else 3,
                                            seed)).encode())
    assert h.hexdigest()[:16] == "93d6fa23baea29c2"
    w = np.random.default_rng(9007).normal(size=(9, 6))
    dist = np.abs(w[:, None] - w[None]).sum(-1)
    assert ref.greedy_groups(dist, 4, 35, 7) == [[3, 7], [5, 6, 8],
                                                 [0, 1, 2, 4]]


def test_change_norms_read_a_host_tree_as_a_device_tree():
    from chipbench import compare
    key = jax.random.PRNGKey(4)
    old = {m: {"w": jax.random.normal(jax.random.fold_in(key, i), (3, 5, 7)),
               "b": jnp.full((3, 7), float(i))}
           for i, m in enumerate(("private", "proxy"))}
    new = jax.tree_util.tree_map(lambda t: t * 1.5 + 0.25, old)
    assert compare.change_norms(new, harness.host_copy(old)) == \
        compare.change_norms(new, old)
    assert compare.dist_gap(np.zeros((1, 1)), np.zeros((1, 1))) == 0.0
