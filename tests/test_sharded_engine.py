"""In-process tests of the sharded federation engine: the pieces that don't
need fake devices — the global compiled-chunk cache (σ-sweep reuse +
cache_token invalidation), the calibrate-then-resume ledger composition,
host-mesh clamping, and the degenerate 1-slice client mesh (which exercises
the whole shard_map path on the single real device, keeping the plumbing
honest inside tier-1's fast set).

The 8-fake-device equivalence scenarios run in four subprocesses, one per
scenario family (``tests/_sharded_equivalence_main.py``), from
``tests/test_equivalence_{schedules,topology,p4,paged}.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.local import LocalStrategy
from repro.config import DPConfig
from repro.engine import (CHUNK_STATS, Engine, FederatedData,
                          PrivacyLedger, ShardedEngine, Strategy,
                          clear_chunk_cache)
from repro.launch.mesh import host_mesh_shape, make_client_mesh


# ---------------------------------------------------------------------------
# compiled-chunk cache: σ sweeps must not re-trace; cache_token bumps must
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def toy():
    rng = np.random.default_rng(0)
    M, feat, classes, n = 6, 12, 3, 32
    protos = rng.normal(size=(classes, feat)).astype(np.float32) * 3
    ys = rng.integers(0, classes, size=(M, n))
    xs = protos[ys] + rng.normal(size=(M, n, feat)).astype(np.float32) * 0.4
    X, Y = xs, ys.astype(np.int32)
    return FederatedData(X, Y, jnp.asarray(X), jnp.asarray(Y))


def _dp_local(sigma):
    return LocalStrategy(feat_dim=12, num_classes=3, lr=0.5,
                         dp_cfg=DPConfig(clip_norm=1.0), sigma=sigma)


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree_util.tree_leaves(tree)]


def test_sigma_sweep_reuses_compiled_chunk(toy, key):
    """ISSUE 4 satellite: a sweep over σ with the same (length, batch_size,
    cache_token, mesh) compiles ONE chunk — σ reaches the trace as a runtime
    argument — and the reused chunk is bit-identical to a fresh compile."""
    clear_chunk_cache()
    finals = {}
    for sigma in (0.5, 0.9, 1.3):
        strat = _dp_local(sigma)
        st, _ = Engine(strat, eval_every=100).fit(
            toy, rounds=6, key=key, batch_size=8, evaluate=False)
        finals[sigma] = st
    assert CHUNK_STATS["traces"] == 1, CHUNK_STATS
    assert CHUNK_STATS["misses"] == 1 and CHUNK_STATS["hits"] == 2

    # σ actually flowed into the reused chunk: the noise differs
    assert not all(np.array_equal(a, b) for a, b in
                   zip(_leaves(finals[0.5]), _leaves(finals[1.3])))

    # reuse is bit-faithful: a cold-cache compile at σ=1.3 matches the state
    # the warm chunk produced
    clear_chunk_cache()
    fresh, _ = Engine(_dp_local(1.3), eval_every=100).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    for a, b in zip(_leaves(fresh), _leaves(finals[1.3])):
        np.testing.assert_array_equal(a, b)


def test_cache_token_bump_retraces(toy, key):
    clear_chunk_cache()
    strat = _dp_local(0.7)
    Engine(strat, eval_every=100).fit(toy, rounds=6, key=key, batch_size=8,
                                      evaluate=False)
    assert CHUNK_STATS["traces"] == 1
    strat.cache_token += 1   # what set_groups does between P4 phases
    Engine(strat, eval_every=100).fit(toy, rounds=6, key=key, batch_size=8,
                                      evaluate=False)
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS


def test_target_epsilon_recalibration_reuses_chunk(toy, key):
    """set_sigma no longer invalidates chunks: two target-ε runs share one
    compiled chunk and still land on their own budgets."""
    clear_chunk_cache()
    spent = {}
    for target in (6.0, 12.0):
        strat = _dp_local(1.0)
        ledger = PrivacyLedger(sigma=1.0, delta=1e-3, sample_rate=0.25)
        _, hist = Engine(strat, eval_every=100, ledger=ledger).fit(
            toy, rounds=8, key=key, batch_size=8, target_epsilon=target)
        spent[target] = hist.metrics["dp_epsilon"][-1]
    # the eval cadence splits 8 rounds into a length-1 and a length-7 chunk:
    # two traces for the FIRST target, pure cache hits for the second
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS
    for target, got in spent.items():
        assert abs(got - target) < 1e-6, spent


def test_sharded_mesh_is_part_of_the_cache_key(toy, key):
    """Same strategy fingerprint, different execution layout → different
    chunk; same layout twice → reuse."""
    clear_chunk_cache()
    mesh = make_client_mesh()   # 1 slice on the test host
    Engine(_dp_local(0.7), eval_every=100).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    ShardedEngine(_dp_local(0.7), eval_every=100, mesh=mesh).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS
    ShardedEngine(_dp_local(0.9), eval_every=100, mesh=mesh).fit(
        toy, rounds=6, key=key, batch_size=8, evaluate=False)
    assert CHUNK_STATS["traces"] == 2, CHUNK_STATS


# ---------------------------------------------------------------------------
# degenerate 1-slice client mesh: the full shard_map path on the real device
# ---------------------------------------------------------------------------

def test_sharded_engine_single_slice_matches_engine(toy, key):
    st1, h1 = Engine(_dp_local(0.6), eval_every=3).fit(
        toy, rounds=7, key=key, batch_size=8)
    st2, h2 = ShardedEngine(_dp_local(0.6), eval_every=3,
                            mesh=make_client_mesh()).fit(
        toy, rounds=7, key=key, batch_size=8)
    assert h1.rounds == h2.rounds and h1.accuracy == h2.accuracy
    for a, b in zip(_leaves(st1), _leaves(st2)):
        np.testing.assert_array_equal(a, b)


def test_sharded_engine_rejects_unkeyed_strategy(toy, key):
    # scaffold/proxyfl are ported now — fabricate a strategy that only has
    # the unkeyed hook to keep the clean-rejection contract covered
    class UnkeyedStrategy(LocalStrategy):
        local_update_keyed = Strategy.local_update_keyed

    strat = UnkeyedStrategy(feat_dim=12, num_classes=3, lr=0.5)
    eng = ShardedEngine(strat, eval_every=100, mesh=make_client_mesh())
    with pytest.raises(NotImplementedError, match="local_update_keyed"):
        eng.fit(toy, rounds=2, key=key, batch_size=8, evaluate=False)


def test_sharded_engine_requires_client_axis(toy):
    import jax as _jax
    mesh = _jax.make_mesh((1, 1), ("data", "model"),
                          devices=_jax.devices()[:1])
    with pytest.raises(ValueError, match="clients"):
        ShardedEngine(_dp_local(0.5), mesh=mesh)


# ---------------------------------------------------------------------------
# ledger: calibrate-then-resume composes onto the restored spend
# ---------------------------------------------------------------------------

def test_calibrate_then_resume_composes_budget(toy, key, tmp_path):
    """Engine.fit double-advance fix: calibration happens AFTER the resume
    branch, for the remaining rounds only, composed on the ledger's restored
    spend — the whole 20-round trajectory lands exactly on the (raised)
    resume budget instead of overshooting it."""

    def make(sigma):
        strat = _dp_local(sigma)
        ledger = PrivacyLedger(sigma=sigma, delta=1e-3, sample_rate=0.25)
        eng = Engine(strat, eval_every=5, checkpoint_dir=str(tmp_path),
                     ledger=ledger)
        return eng, strat

    eng, strat = make(1.0)
    eng.fit(toy, rounds=10, key=key, batch_size=8, target_epsilon=8.0)
    sigma1 = strat.sigma
    assert abs(eng.ledger.epsilon() - 8.0) < 1e-6

    eng2, strat2 = make(sigma1)   # resume at the σ the first run trained with
    _, hist = eng2.fit(toy, rounds=20, key=key, batch_size=8, resume=True,
                       target_epsilon=12.0)
    assert eng2.ledger.rounds_seen == 20
    # rounds 0..10 restored at σ1 already spent ε=8; the recalibrated σ fits
    # rounds 10..20 into the remaining budget. Pre-fix, calibration ran
    # before the resume advanced start_round (sizing σ for 20 fresh rounds)
    # and ignored the restored spend — the trajectory missed the target.
    assert abs(hist.metrics["dp_epsilon"][-1] - 12.0) < 1e-6
    # the recalibration solved a different problem than run 1's (compose onto
    # ε=8 of restored spend), so it found a different σ
    assert abs(strat2.sigma - sigma1) > 1e-3


# ---------------------------------------------------------------------------
# host-mesh clamping (pure) + client-mesh construction
# ---------------------------------------------------------------------------

def test_host_mesh_shape_explicit_clamping():
    assert host_mesh_shape(4, 2, 8) == (4, 2)
    assert host_mesh_shape(16, 16, 8) == (8, 1)    # data eats every device
    assert host_mesh_shape(3, 4, 8) == (3, 2)      # model fits what's left
    assert host_mesh_shape(0, 4, 8) == (1, 4)      # no n//0 crash
    assert host_mesh_shape(2, 0, 8) == (2, 1)
    assert host_mesh_shape(5, 5, 1) == (1, 1)
    assert host_mesh_shape(1, 1, 0) == (1, 1)
    d, m = host_mesh_shape(3, 3, 8)
    assert d * m <= 8 and d >= 1 and m >= 1


def test_make_host_mesh_on_real_devices():
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(4, 4)   # clamps to whatever the host has
    n = len(jax.devices())
    d, m = mesh.shape["data"], mesh.shape["model"]
    assert (d, m) == host_mesh_shape(4, 4, n)


def test_make_client_mesh_shape():
    mesh = make_client_mesh()
    assert tuple(mesh.shape.keys()) == ("clients",)
    assert mesh.shape["clients"] == len(jax.devices())
    assert make_client_mesh(1).shape["clients"] == 1


def test_client_mesh_axes_are_auto_and_devolve_cleanly():
    """Every mesh builder uses Auto axes. Under jax's default Explicit axes a
    client stack moved off the mesh onto one device (what
    ``ShardedEngine._finalize_state`` does before eval) keeps ``@clients`` in
    its type, and vmapping it beside an unsharded array fails."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec
    from repro.config import MeshConfig
    from repro.launch.mesh import make_host_mesh, make_mesh
    mesh = make_client_mesh()
    for m in (mesh, make_host_mesh(1, 1),
              make_mesh(MeshConfig(data=1, model=1))):
        assert set(m.axis_types) == {AxisType.Auto}, m
    n = mesh.shape["clients"]
    x = jax.device_put(jnp.arange(8.0 * n).reshape(4 * n, 2),
                       NamedSharding(mesh, PartitionSpec("clients")))
    x1 = jax.device_put(x, jax.devices()[0])
    out = jax.jit(jax.vmap(lambda a, b: a * b))(x1, jnp.ones((4 * n, 2)))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
