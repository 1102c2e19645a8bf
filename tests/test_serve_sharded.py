"""Multi-device sharded serving tests (slot-pool sharding over the batch axis).

Pinned invariants:
  1. a 2-device engine (slot-axis NamedSharding over a 1-D 'data' mesh) is
     greedy token-identical to BOTH the single-device continuous engine and
     the static oracle, for dense and MLA, slab and block-paged pools;
  2. compile counters stay exact under the mesh: fused=1 / decode=1 /
     prefill=0 — sharding must not introduce retracing;
  3. admission placement is least-loaded-first across device slot ranges
     (one hot device cannot strand free slots elsewhere), and the paged
     pool's per-device block ranges keep reservations device-local;
  4. ``devices=1`` builds no mesh and stays bit-identical to the unsharded
     engine (the pools collapse to a single global FIFO range).

The mesh tests need >= 2 jax devices and skip otherwise; CI runs them in a
dedicated step with XLA_FLAGS=--xla_force_host_platform_device_count=2, and
``test_sharded_suite_under_forced_host_devices`` (slow) re-runs this module
in a 2-device subprocess so RUN_SLOW tier-1 covers SPMD even on one device.
Host-side range/placement accounting needs no devices and always runs.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs.registry import get_config, reduce_config
from repro.data.synthetic import DataConfig, batch_at
from repro.models.transformer import make_model
from repro.serve.engine import ContinuousEngine, ServeConfig, static_reference
from repro.serve.kv_cache import BlockPagedKVPool, SlotKVPool
from repro.serve.scheduler import Request
from repro.serve.workload import required_max_seq

from _serve_helpers import assert_exact_compile_counters

REPO = Path(__file__).resolve().parents[1]
CHUNK = 4
TWO_DEV = jax.device_count() >= 2
requires_mesh = pytest.mark.skipif(
    not TWO_DEV,
    reason="needs >= 2 devices "
    "(export XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)


@pytest.fixture(scope="module")
def dense():
    cfg = reduce_config(get_config("internlm2-1.8b"))
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mla():
    cfg = reduce_config(get_config("minicpm3-4b"))
    model = make_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _prompt(cfg, length, seed):
    data = DataConfig(vocab=cfg.vocab, seq_len=length, global_batch=1, seed=seed)
    return np.asarray(batch_at(data, 0)["tokens"][0], np.int32)


def _mixed_requests(cfg, max_new=6):
    # >= 4 distinct prompt lengths, none grid-aligned, staggered arrivals,
    # more requests than per-device slots -> placement and recycling both
    # exercise; max_new large enough that all-decode ticks hit the fast path
    lens = [5, 9, 14, 22, 7]
    return [
        Request(id=i, tokens=_prompt(cfg, L, seed=500 + i), max_new_tokens=max_new,
                arrival_step=i)
        for i, L in enumerate(lens)
    ]


# --------------------------------------------- host-side range accounting ---
def test_slot_pool_ranges_and_least_loaded_pick(dense):
    _, model, _ = dense
    pool = SlotKVPool(model, num_slots=4, max_seq=16, num_devices=2)
    assert pool.per_device_slots == 2
    assert [pool.device_of(s) for s in range(4)] == [0, 0, 1, 1]
    # empty pool: tie breaks toward device 0, FIFO within the range
    assert pool.pick_device() == 0
    assert pool.allocate(device=0) == 0
    # device 0 now has 1 free, device 1 has 2 -> least-loaded is 1
    assert pool.pick_device() == 1
    assert pool.allocate(device=1) == 2
    assert pool.pick_device() in (0, 1)  # tied again at 1 free each
    pool.free(0)
    assert pool.free_slots_on(0) == 2 and pool.free_slots_on(1) == 1
    assert pool.pick_device() == 0


def test_slot_pool_rejects_indivisible_slots(dense):
    _, model, _ = dense
    with pytest.raises(ValueError, match="divide evenly"):
        SlotKVPool(model, num_slots=3, max_seq=16, num_devices=2)


def test_paged_pool_per_device_blocks_and_reservations(dense):
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=4, max_seq=16, block_size=4,
                            num_blocks=8, num_devices=2)
    assert pool.blocks_per_device == 4 and pool.max_request_blocks == 4
    # device 0's range is blocks [0, 4), device 1's is [4, 8)
    s0 = pool.allocate(reserve_tokens=16, device=0)   # 4 blocks: fills dev 0
    assert pool.device_of(s0) == 0
    assert not pool.can_reserve(4, device=0)          # dev 0 ledger is full
    assert pool.can_reserve(16, device=1)             # dev 1 untouched
    assert pool.pick_device(4) == 1                   # placement skips dev 0
    s1 = pool.allocate(reserve_tokens=8, device=1)
    pool.ensure(s0, 6)                                # 2 blocks from dev 0
    pool.ensure(s1, 6)                                # 2 blocks from dev 1
    assert list(pool.tables[s0, :2]) == [0, 1]
    assert list(pool.tables[s1, :2]) == [4, 5]        # device-local blocks
    assert pool.blocks_in_use_on(0) == 2 and pool.blocks_in_use_on(1) == 2
    pool.free(s0)
    # blocks recycle to their OWN device's FIFO list
    assert pool.free_blocks_on(0) == 4 and pool.free_blocks_on(1) == 2
    s2 = pool.allocate(reserve_tokens=4, device=0)
    pool.ensure(s2, 2)
    assert pool.tables[s2, 0] == 2                    # dev-0 FIFO continues
    pool.free(s1)
    pool.free(s2)
    assert pool.blocks_in_use == 0 and pool.blocks_reserved == 0


def test_paged_pool_rounds_arena_to_device_multiple(dense):
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=2, max_seq=16, block_size=4,
                            num_blocks=7, num_devices=2)
    assert pool.num_blocks == 8  # rounded up so the block axis shards evenly
    assert pool.blocks_per_device == 4


def test_legacy_allocate_checks_the_popped_slots_device(dense):
    # a no-device allocate() must check the reservation ledger of the device
    # the FIFO-head slot actually lands on — not device 0's (which may be
    # full while the head slot's device has plenty of headroom)
    _, model, _ = dense
    pool = BlockPagedKVPool(model, num_slots=4, max_seq=16, block_size=4,
                            num_blocks=8, num_devices=2)
    pool.allocate(reserve_tokens=16, device=0)  # device 0 fully reserved
    pool.allocate(device=0)                     # drain device 0's free slots
    # FIFO head is now slot 2 (device 1): legacy call must succeed
    s = pool.allocate(reserve_tokens=8)
    assert pool.device_of(s) == 1
    # and a failing legacy call restores FIFO order
    pool.allocate(reserve_tokens=4)             # slot 3: 1 more dev-1 block
    pool.free(s)                                # dev 1 ledger back to 1/4
    with pytest.raises(RuntimeError, match="device 1"):
        pool.allocate(reserve_tokens=16)        # head is dev 1: 4 > 3 free
    assert pool._free_slots[0] == s             # pushed back at the front


def test_force_host_devices_parses_both_flag_forms(monkeypatch):
    # the pre-jax-init hook must honor --devices=N as well as --devices N
    # (argparse accepts both; the hook silently doing nothing for one form
    # crashed the documented smoke command)
    from repro.launch._host_devices import devices_from_argv, force_host_devices

    assert devices_from_argv(["prog", "--devices", "2"]) == 2
    assert devices_from_argv(["prog", "--devices=3"]) == 3
    assert devices_from_argv(["prog"]) is None
    assert devices_from_argv(["prog", "--devices", "x"]) is None
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    force_host_devices(["prog", "--devices=2"])
    assert "--xla_force_host_platform_device_count=2" in os.environ["XLA_FLAGS"]
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    force_host_devices(["prog", "--devices", "2"])  # operator setting wins
    assert os.environ["XLA_FLAGS"] == "--xla_force_host_platform_device_count=8"


def test_single_device_pools_keep_global_fifo(dense):
    # num_devices=1: one range covering the whole pool, FIFO order exactly
    # the historical global order (devices=1 bit-identity rests on this)
    _, model, _ = dense
    pool = SlotKVPool(model, num_slots=3, max_seq=16)
    assert pool.num_devices == 1 and pool.per_device_slots == 3
    assert [pool.allocate(device=pool.pick_device()) for _ in range(3)] == [0, 1, 2]
    pool.free(1)
    pool.free(0)
    assert pool.allocate(device=pool.pick_device()) == 1  # FIFO, not LIFO


# ----------------------------------------------------- 2-device SPMD tests ---
@requires_mesh
def test_sharded_params_replicated_once(dense):
    """devices=2: every params leaf a tick receives sits on the engine's
    replicated NamedSharding, and every later tick receives those same
    buffers under a transfer guard — params move once, at construction,
    never per tick."""
    cfg, model, params = dense
    reqs = _mixed_requests(cfg)
    engine = ContinuousEngine(model, params, num_slots=2,
                              max_seq=required_max_seq(reqs),
                              cfg=ServeConfig(), chunk=CHUNK, devices=2)
    received = []

    def recording(jitted):
        def call(tick_params, *args):
            if received:
                with jax.transfer_guard("disallow"):
                    out = jitted(tick_params, *args)
            else:
                out = jitted(tick_params, *args)
            received.append(jax.tree.leaves(tick_params))
            return out
        return call

    for step in (engine._fused, engine._decode):
        step._jitted = recording(step._jitted)
    engine.run(reqs)
    assert len(received) >= 2
    first = received[0]
    assert all(leaf.sharding == engine._sh_rep for leaf in first)
    for leaves in received[1:]:
        assert all(a is b for a, b in zip(leaves, first))


@requires_mesh
@pytest.mark.parametrize("family", ["dense", "mla"])
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "slab"])
def test_sharded_greedy_identity_and_counters(dense, mla, family, paged):
    """2-device engine == single-device engine == static oracle (greedy),
    with exact compile counters under the mesh, for slab and paged pools."""
    cfg, model, params = dense if family == "dense" else mla
    scfg = ServeConfig()
    reqs = _mixed_requests(cfg)
    max_seq = required_max_seq(reqs)

    sharded = ContinuousEngine(model, params, num_slots=4, max_seq=max_seq,
                               cfg=scfg, chunk=CHUNK, devices=2, paged=paged)
    assert sharded.mesh is not None and sharded.num_devices == 2
    comps2 = {c.request_id: c.tokens for c in sharded.run(reqs)}

    single = ContinuousEngine(model, params, num_slots=4, max_seq=max_seq,
                              cfg=scfg, chunk=CHUNK, devices=1, paged=paged)
    assert single.mesh is None
    comps1 = {c.request_id: c.tokens for c in single.run(reqs)}

    ref = static_reference(model, params, reqs, scfg)
    assert comps2.keys() == comps1.keys() == ref.keys()
    for rid in ref:
        assert np.array_equal(comps2[rid], ref[rid]), f"req {rid} vs oracle"
        assert np.array_equal(comps2[rid], comps1[rid]), f"req {rid} vs 1-dev"

    m = sharded.metrics()
    assert m["num_devices"] == 2 and m["per_device_slots"] == 2
    assert_exact_compile_counters(m)
    assert 0.0 < m["shard_balance"] <= 1.0
    assert sum(m["device_admits"]) == len(reqs)
    if paged:
        assert sharded.pool.blocks_in_use == 0  # drained on both shards


@requires_mesh
def test_least_loaded_admission_places_across_devices(dense):
    """Two simultaneously-arriving requests must land on DIFFERENT devices
    (slots 0 and 2 on a 4-slot/2-device pool), not fill device 0 first."""
    cfg, model, params = dense
    reqs = [
        Request(id=i, tokens=_prompt(cfg, 8, seed=520 + i), max_new_tokens=8,
                arrival_step=0)
        for i in range(2)
    ]
    engine = ContinuousEngine(model, params, num_slots=4, max_seq=16,
                              cfg=ServeConfig(), chunk=CHUNK, devices=2)
    for r in reqs:
        engine.submit(r)
    engine.step()
    assert engine.device_occupancy() == [1, 1]
    occupied = [s for s, st in enumerate(engine._slots) if st is not None]
    assert occupied == [0, 2]  # FIFO head of each device's range
    assert list(engine._device_admits) == [1, 1]
    engine.run([])  # drain so the pool is clean


@requires_mesh
def test_sharded_cache_leaves_are_slot_sharded(dense):
    """The pool actually places leaves with a slot-axis NamedSharding: each
    leaf's batch/slot (or block-arena) dim is split over the 'data' axis."""
    cfg, model, params = dense
    engine = ContinuousEngine(model, params, num_slots=4, max_seq=16,
                              devices=2)
    assert engine.paged
    axes = model.paged_cache_logical_axes()
    leaves_ax = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = jax.tree.leaves(engine.pool.cache)
    assert len(leaves) == len(leaves_ax)
    for leaf, ax in zip(leaves, leaves_ax):
        dim = ax.index("batch")
        spec = leaf.sharding.spec
        assert len(spec) > dim and spec[dim] is not None, (ax, spec)
    # tick state shards over slots too
    assert engine._pos_dev.sharding.spec[0] is not None


@requires_mesh
def test_devices_exceeding_visible_raises():
    cfg = reduce_config(get_config("internlm2-1.8b"))
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="devices"):
        ContinuousEngine(model, params, num_slots=jax.device_count() + 1,
                         max_seq=16, devices=jax.device_count() + 1)


_BUILD_CHECK = """
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch import serve
from repro.serve.engine import ContinuousEngine
args = serve.parse_args(["--smoke", "--continuous", "--devices", "2"])
cfg, model, params, scfg = serve.build(args)
leaves = jax.tree.leaves(params)
engine = ContinuousEngine(model, params, num_slots=2, max_seq=64, cfg=scfg,
                          chunk=4, devices=2)
rep = NamedSharding(engine.mesh, P())
assert all(x.sharding == rep and x.is_fully_replicated for x in leaves)
assert all(a is b for a, b in zip(jax.tree.leaves(engine.params), leaves))
single = serve.build(serve.parse_args(["--smoke", "--continuous"]))[2]
ref = jax.tree.leaves(single)
assert all((a == b).all() for a, b in zip(leaves, ref))
print("BUILD_OK")
"""


def test_build_makes_params_on_the_replicated_mesh():
    """``launch/serve.py --devices 2`` makes the params straight onto the
    replicated slot-mesh sharding, with the values ``--devices 1`` makes,
    and the engine serves from those buffers without a copy: no
    device ever holds a second set.  Runs in a child process with two
    forced host devices, so it runs on one-device hosts too."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", _BUILD_CHECK], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "BUILD_OK" in r.stdout, r.stderr[-3000:]


# ------------------------------------------------- subprocess SPMD driver ---
@pytest.mark.slow  # jax re-init + 4 engine compiles in a child process
@pytest.mark.skipif(TWO_DEV, reason="already running under >= 2 devices")
def test_sharded_suite_under_forced_host_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         str(REPO / "tests" / "test_serve_sharded.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=3000,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout[-3000:]}\nstderr:\n{r.stderr[-2000:]}"
