"""The GN Pallas kernels compile for a TPU v5e at internlm2-1.8b's widths.

Interpret mode runs a kernel's body in Python and accepts programs the TPU
compiler refuses (unsupported shape casts, unaligned slices, too much VMEM),
so every kernel of the serving path is also compiled here for a described
v5e chip, with no chip attached: 16 query / 8 KV heads, head_dim 128, paged
blocks of 16 tokens, fp and int8 arenas, a 16-token chunk and C=1 decode.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and the tests run
under several workers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gn_attention.ops import gn_attention
from repro.kernels.gn_layernorm.ops import gn_layernorm, gn_rmsnorm
from repro.kernels.gn_paged_attention.ops import gn_paged_attention_chunk
from repro.kernels.gn_softmax.ops import gn_softmax

H, HKV, DH, D_MODEL = 16, 8, 128, 2048   # internlm2-1.8b
N_SEQS, BLOCK, NUM_BLOCKS, MAX_BT = 4, 16, 64, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of any cache the environment set
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel_compiles(fn, *args, **kwargs):
    compiled = fn.lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gn_softmax_compiles(one_chip):
    scores = _sds((N_SEQS, H, 16, 256), jnp.float32, one_chip)
    _assert_kernel_compiles(gn_softmax, scores)


@pytest.mark.parametrize("norm", [gn_layernorm, gn_rmsnorm],
                         ids=["layernorm", "rmsnorm"])
def test_gn_norm_compiles(one_chip, norm):
    x = _sds((64, D_MODEL), jnp.bfloat16, one_chip)
    gamma = _sds((D_MODEL,), jnp.float32, one_chip)
    _assert_kernel_compiles(jax.jit(norm), x, gamma)


def test_gn_attention_compiles(one_chip):
    q = _sds((1, H, 256, DH), jnp.bfloat16, one_chip)
    kv = _sds((1, HKV, 256, DH), jnp.bfloat16, one_chip)
    _assert_kernel_compiles(gn_attention, q, kv, kv, causal=True)


@pytest.mark.parametrize("chunk", [16, 1], ids=["C16", "C1"])
@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_gn_paged_attention_chunk_compiles(one_chip, kv_dtype, chunk):
    q = _sds((N_SEQS, chunk, H, DH), jnp.bfloat16, one_chip)
    arena_dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    arena = _sds((NUM_BLOCKS, BLOCK, HKV, DH), arena_dtype, one_chip)
    tables = _sds((N_SEQS, MAX_BT), jnp.int32, one_chip)
    per_seq = _sds((N_SEQS,), jnp.int32, one_chip)
    scales = None
    if kv_dtype == "int8":
        scale = _sds((NUM_BLOCKS,), jnp.float32, one_chip)
        scales = (scale, scale)
    _assert_kernel_compiles(gn_paged_attention_chunk, q, arena, arena, tables,
                            per_seq, per_seq, scales=scales)


def _relayouts_feeding_kernels(text: str, arena_elems: int):
    """(number of Pallas calls, arena-sized transposes, copies and pads on
    the producer chains of their operands) in compiled HLO ``text``.

    A chain is followed back to a parameter, the layer loop's state, or the
    loop's per-layer slice of the stacked arenas (which every read has); a
    fusion counts when it holds an arena-sized relayout."""
    import re

    defs, comps, comp = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"\s*(?:ENTRY )?%(\S+) .*\{\s*$", line)
        if head and " = " not in line.split("{")[0]:
            comp = head.group(1)
            comps[comp] = []
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w\-]+)\((.*)", line)
        if not m:
            continue
        name, shape, op, rest = m.groups()
        size = max((int(np.prod([int(d) for d in dims.split(",") if d]))
                    for dims in re.findall(r"\w+\[([\d,]*)\]", shape)), default=0)
        operands = re.findall(r"%([\w.\-]+)", re.split(r"\), \w+=", rest)[0])
        calls = re.search(r"calls=%([\w.\-]+)", rest)
        defs[name] = (op, size, operands, calls and calls.group(1), line)
        comps[comp].append((op, size))
    relayout = {"copy", "transpose", "pad"}
    kernels, bad = 0, []
    for op, _, operands, _, line in defs.values():
        if op != "custom-call" or "tpu_custom_call" not in line:
            continue
        kernels += 1
        seen, stack = set(), list(operands)
        while stack:
            name = stack.pop()
            if name in seen or name not in defs:
                continue
            seen.add(name)
            op, size, operands, calls, _ = defs[name]
            if size < arena_elems or op in ("parameter", "get-tuple-element"):
                continue
            inner = comps.get(calls, [])
            if op in relayout or any(o in relayout and s >= arena_elems for o, s in inner):
                bad.append(name)
            if not any(o == "dynamic-slice" for o, _ in inner):
                stack.extend(operands)
    return kernels, bad


def _served_tick_text(sharding, arch, n, chunk, num_blocks, kv_dtype, read_path):
    """Optimized HLO of the whole serving tick of ``arch`` at full widths
    (blocks of 64, a 64-block horizon, sentinels on) over ``read_path``."""
    from repro.configs.registry import get_config
    from repro.models.transformer import make_model

    block, max_bt = 64, 64
    model = make_model(get_config(arch))
    put = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: _sds(s.shape, s.dtype, sharding), tree)
    params = put(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = put(model.paged_cache_specs(n, num_blocks, block, max_bt * block,
                                        kv_dtype=kv_dtype))

    def tick(params, cache, tokens, positions, n_valid, tables):
        return model.fused_step_slots_paged(params, cache, tokens, positions,
                                            n_valid, tables, sentinel=True,
                                            read_path=read_path)

    per_seq = _sds((n,), jnp.int32, sharding)
    return jax.jit(tick, donate_argnums=(1,)).lower(
        params, cache, _sds((n, chunk), jnp.int32, sharding), per_seq, per_seq,
        _sds((n, max_bt), jnp.int32, sharding)).compile().as_text()


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
@pytest.mark.parametrize("n,chunk,num_blocks", [(8, 64, 400), (16, 1, 320)],
                         ids=["docqa-fused", "chat-decode"])
def test_served_paged_read_compiles_without_arena_copies(one_chip, n, chunk,
                                                         num_blocks, kv_dtype):
    """The whole serving tick at the benchmark cells' shapes (internlm2-1.8b,
    24 layers, blocks of 64, a 64-block horizon, sentinels on) with the
    kernel read: every layer's read is the Pallas kernel, and no arena-sized
    transpose, pad or copy feeds it: it reads the pool's arena in place."""
    text = _served_tick_text(one_chip, "internlm2-1.8b", n, chunk, num_blocks,
                             kv_dtype, "pallas")
    kernels, relayouts = _relayouts_feeding_kernels(text, num_blocks * 64 * HKV * DH)
    assert kernels == 1  # the layer loop's body
    assert not relayouts, relayouts


@pytest.mark.parametrize("kv_dtype", ["fp", "int8"])
def test_head_dim_64_keeps_the_streamed_read_on_one_tpu(one_chip, monkeypatch,
                                                        kv_dtype):
    """stablelm-1.6b (32 KV heads of dim 64) on one TPU: the kernel would
    pad a copy of the arena to 128 lanes every layer, so the dispatch keeps
    the streamed read, and the chat decode tick compiles for the v5e at its
    widths with no kernel in it."""
    from repro.configs.registry import get_config
    from repro.models import attention
    from repro.models.transformer import make_model

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    read = make_model(get_config("stablelm-1.6b")).paged_read_path_on(1)
    assert read == "streamed"
    text = _served_tick_text(one_chip, "stablelm-1.6b", 16, 1, 128, kv_dtype, read)
    assert "tpu_custom_call" not in text


def test_paged_layer_scopes_survive_the_tpu_compiler(one_chip):
    """The v5e compiler's optimized HLO of one paged layer step (streamed
    read, sentinels on) still names every layer scope in the metadata of
    its fusions: the device trace reads each operation's scopes from it."""
    import re

    from repro.configs.registry import get_config
    from repro.models.attention import attn_paged_chunk
    from repro.models.layers import apply_mlp, apply_norm, spec_struct
    from repro.models.transformer import make_model
    from repro.serve import tracing

    cfg = get_config("internlm2-1.8b")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model) == (H, HKV, DH, D_MODEL)
    specs = spec_struct(make_model(cfg)._block_specs())
    lp = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), specs)

    def layer(lp, arena_k, arena_v, x, positions, n_valid, tables):
        h = apply_norm(cfg, lp["ln1"], x)
        y, arenas, health = attn_paged_chunk(cfg, lp["mixer"], arena_k, arena_v, h,
                                             positions, n_valid, tables, probe=True)
        x = x + y
        return x + apply_mlp(cfg, lp["mlp"], apply_norm(cfg, lp["ln2"], x)), arenas, health

    arena = _sds((NUM_BLOCKS, BLOCK, HKV, DH), jnp.bfloat16, one_chip)
    x = _sds((N_SEQS, 16, D_MODEL), jnp.bfloat16, one_chip)
    tables = _sds((N_SEQS, MAX_BT), jnp.int32, one_chip)
    per_seq = _sds((N_SEQS,), jnp.int32, one_chip)
    text = jax.jit(layer).lower(lp, arena, arena, x, per_seq, per_seq, tables).compile().as_text()
    fused = re.findall(r"= \S+ fusion\(.*?op_name=\"([^\"]*)\"", text)
    seen = {c for path in fused for c in path.split("/")}
    want = set(tracing.SCOPES) - {tracing.LM_HEAD}  # the head is not in a layer
    assert want <= seen, sorted(want - seen)
