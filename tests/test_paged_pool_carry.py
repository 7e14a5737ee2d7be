"""The paged forward carries the KV pool through its layer scan.

``forward_paged`` scatters into and gathers from the pool's leaves
STACKED over layers, addressed ``(layer, block, offset)``, with the
leaves in the layer scan's carry. Two things hold it there:

- a plain per-layer reference written here (a Python loop over layers on
  ``pool.k[l]``: slice the layer out, scatter, gather, stack the layers
  back) gives the same bits, logits and every pool leaf, for every pool
  variant, with a share of the entries on the drop sentinel;
- the compiled step keeps no second pool: its temporaries stay under a
  quarter of the pool's bytes (the xs/ys form of the scan needed more
  than a whole pool);
- compiled for a v5e (described, not attached) with the Pallas kernel
  reading the pool, the step at the benchmark cells' shapes holds no
  temporary of a pool's or a layer slice's size, hands the kernel the
  stacked leaves themselves, and copies no array of a leaf's shape.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from senweaver_ide_tpu.models import transformer as tf
from senweaver_ide_tpu.models.config import get_config
from senweaver_ide_tpu.ops.attention import attention
from senweaver_ide_tpu.ops.norms import rms_norm
from senweaver_ide_tpu.ops.rotary import rope_cos_sin
from senweaver_ide_tpu.rollout.sampler import SampleParams
from senweaver_ide_tpu.rollout import engine as eng
from senweaver_ide_tpu.rollout.paged_kv import (PagedKVPool, init_paged_pool,
                                                kv_row_bytes,
                                                resolve_block_size,
                                                stored_kv_heads)

NUM_BLOCKS, BLOCK_SIZE, ROWS, TABLE_WIDTH = 12, 4, 3, 4


def _config():
    # three layers: a prefix of one and a tail of two for the ladder, and
    # a "next layer" after each of the first two for the sentinel
    return dataclasses.replace(get_config("tiny-test"), num_layers=3)


def _random_pool(config, key, kv_dtype, per_layer):
    """A pool whose every element is random, so a write that lands in the
    wrong place, or a gather that reads the wrong layer, changes bits."""
    pool = init_paged_pool(config, NUM_BLOCKS, BLOCK_SIZE, kv_dtype,
                           per_layer)
    leaves, treedef = jax.tree_util.tree_flatten(pool)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        if leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128,
                                          jnp.int32).astype(jnp.int8))
        elif leaf.ndim == 4:                     # absmax scales: positive
            out.append(jax.random.uniform(k, leaf.shape, jnp.float32,
                                          1e-3, 2e-2))
        else:
            out.append(jax.random.normal(k, leaf.shape, jnp.float32)
                       .astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _random_banks(config, key, slots=2, rank=4):
    """One rung of a LoRA bank as rollout/adapter_pool lays it out:
    leaves (L, slots + 1, d_in, r) / (L, slots + 1, r, d_out), slot 0
    the null adapter."""
    c = config
    dims = {"wq": (c.hidden_size, c.q_dim), "wv": (c.hidden_size, c.kv_dim),
            "wo": (c.q_dim, c.hidden_size)}
    bank = {}
    for i, (name, (d_in, d_out)) in enumerate(dims.items()):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        a = 0.1 * jax.random.normal(
            ka, (c.num_layers, slots + 1, d_in, rank), c.dtype)
        b = 0.1 * jax.random.normal(
            kb, (c.num_layers, slots + 1, rank, d_out), c.dtype)
        bank[name + "_lora_a"] = a.at[:, 0].set(0)
        bank[name + "_lora_b"] = b.at[:, 0].set(0)
    return (bank,)


def _batch(decode_only):
    """A flat token batch over three rows. Row 0 prefills a five-token
    chunk (or decodes), rows 1 and 2 decode deep into their tables; the
    entries marked dropped address the sentinel block ``NUM_BLOCKS``,
    each at an offset and beside a block whose neighbours a wrong write
    would hit."""
    tables = np.array([[3, 7, 0, 0], [5, 1, 9, 0], [11, 2, 6, 10]], np.int32)
    if decode_only:                    # the kernel: one entry a row
        seq_row = np.array([0, 1, 2], np.int32)
        positions = np.array([4, 9, 14], np.int32)
        dropped = np.array([False, True, False])
    else:
        seq_row = np.array([0, 0, 0, 0, 0, 1, 2, 1, 2, 0], np.int32)
        positions = np.array([0, 1, 2, 3, 4, 9, 14, 10, 15, 5], np.int32)
        dropped = np.array([False, False, True, False, False, False, False,
                            True, True, True])
    block = tables[seq_row, positions // BLOCK_SIZE]
    write_block = np.where(dropped, NUM_BLOCKS, block).astype(np.int32)
    write_off = (positions % BLOCK_SIZE).astype(np.int32)
    tokens = (np.arange(len(seq_row), dtype=np.int32) * 37 + 11) % 512
    return dict(tokens=tokens, tables=tables, seq_row=seq_row,
                positions=positions, write_block=write_block,
                write_off=write_off), dropped


def _reference_layer(c, lp, x, cos, sin, leaves, b, use_kernel, ad, ad_ids):
    """One block on ONE layer's pool ``(num_blocks, block_size, ...)``:
    the semantics the stacked form has to keep."""
    t = x.shape[0]
    h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)
    q, k, v = tf._qkv(c, lp, h, cos, sin, ad, ad_ids)
    if len(leaves) == 4:
        kq, ks = tf.quantize_pool_kv(k[:, 0], leaves[0].dtype)
        vq, vs = tf.quantize_pool_kv(v[:, 0], leaves[1].dtype)
        new = (kq, vq, ks, vs)
    else:
        new = (k[:, 0].astype(leaves[0].dtype),
               v[:, 0].astype(leaves[1].dtype))
    leaves = tuple(
        leaf.at[b["write_block"], b["write_off"]].set(val, mode="drop")
        for leaf, val in zip(leaves, new))
    tbl = b["tables"][b["seq_row"]]
    if use_kernel:
        from senweaver_ide_tpu.ops.paged_attention import paged_flash_decode
        k_scale, v_scale = leaves[2:] or (None, None)
        out = paged_flash_decode(q[:, 0], leaves[0], leaves[1], tbl,
                                 b["positions"] + 1, k_scale=k_scale,
                                 v_scale=v_scale)[:, None]
    else:
        width = tbl.shape[1] * BLOCK_SIZE
        seqs = [leaf[tbl].reshape((t, width) + leaf.shape[2:])
                for leaf in leaves]
        k_seq, v_seq = seqs[:2]
        if len(leaves) == 4:
            k_seq = tf.dequantize_pool_kv(k_seq, seqs[2], x.dtype)
            v_seq = tf.dequantize_pool_kv(v_seq, seqs[3], x.dtype)
        valid = jnp.arange(width)[None, :] < b["positions"][:, None] + 1
        out = attention(q, k_seq.astype(x.dtype), v_seq.astype(x.dtype),
                        q_offset=b["positions"], kv_mask=valid, causal=True)
    attn_in = out.reshape(t, 1, c.q_dim)
    attn_out = tf._dense(attn_in, lp, "wo", "bse,ed->bsd")
    x = x + tf._with_adapter(attn_out, attn_in, ad, ad_ids, "wo")
    x, *_ = tf._mlp(c, lp, x)      # (x, aux, MoEStats | None, None)
    return x, leaves


def _reference_forward_paged(params, c, pool, b, use_kernel, adapters,
                             adapter_ids):
    """A Python loop over layers, each on its own slice of the pool."""
    take = lambda tree, l: jax.tree_util.tree_map(lambda a: a[l], tree)
    with jax.default_matmul_precision(c.matmul_precision):
        x = params["embed"][b["tokens"]][:, None, :]
        cos, sin = rope_cos_sin(b["positions"][:, None], c.head_dim,
                                c.rope_theta, scaling=c.rope_scaling)
        names = ("k", "v") if pool.k_scale is None else (
            "k", "v", "k_scale", "v_scale")
        done = {}
        for l in range(c.num_layers):
            group, at = ((("k_hi", "v_hi"), l) if l < pool.hi_layers
                         else (names, l - pool.hi_layers))
            x, leaves = _reference_layer(
                c, take(params["layers"], l), x, cos, sin,
                tuple(getattr(pool, n)[at] for n in group), b, use_kernel,
                take(adapters, l), adapter_ids)
            for n, leaf in zip(group, leaves):
                done.setdefault(n, []).append(leaf)
        x = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"]) \
            if params.get("lm_head") is None else tf._dense(
                x, params, "lm_head", "bsd,dv->bsv")
    return (logits[:, 0].astype(jnp.float32),
            pool._replace(**{n: jnp.stack(v) for n, v in done.items()}))


VARIANTS = {
    "bf16": dict(kv_dtype="bf16"),
    "int8": dict(kv_dtype="int8"),
    "fp8": dict(kv_dtype="fp8"),
    "prefix-ladder": dict(kv_dtype="int8",
                          per_layer=("bf16", "int8", "int8")),
    "lora-banks": dict(kv_dtype="bf16", lora=True),
    "kernel": dict(kv_dtype="bf16", use_kernel=True),
    "kernel-int8": dict(kv_dtype="int8", use_kernel=True),
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_forward_paged_matches_per_layer_reference_bit_for_bit(variant):
    v = VARIANTS[variant]
    use_kernel = v.get("use_kernel", False)
    c = _config()
    key = jax.random.PRNGKey(7)
    params = tf.init_params(c, jax.random.fold_in(key, 1))
    pool = _random_pool(c, jax.random.fold_in(key, 2), v["kv_dtype"],
                        v.get("per_layer"))
    batch, dropped = _batch(decode_only=use_kernel)
    b = {k: jnp.asarray(a) for k, a in batch.items()}
    adapters = adapter_ids = None
    if v.get("lora"):
        adapters = _random_banks(c, jax.random.fold_in(key, 3))
        adapter_ids = (jnp.asarray(np.arange(len(dropped)) % 3, jnp.int32),)

    ref = jax.jit(_reference_forward_paged, static_argnums=(1, 4))
    want_logits, want_pool = ref(params, c, pool, b, use_kernel, adapters,
                                 adapter_ids)
    run = jax.jit(tf.forward_paged, static_argnames=("config", "use_kernel"))
    got_logits, got_pool = run(
        params, config=c, pool=pool, use_kernel=use_kernel,
        adapters=adapters, adapter_ids=adapter_ids, **b)

    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    assert got_pool._fields == want_pool._fields
    for name, got, want, before in zip(got_pool._fields, got_pool,
                                       want_pool, pool):
        if want is None:
            assert got is None, name
            continue
        got, before = np.asarray(got), np.asarray(before)
        assert got.dtype == np.asarray(want).dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, np.asarray(want), err_msg=name)
        # A dropped entry changes no block of any layer: every (layer,
        # block, offset) the batch did not address holds its old bits —
        # block 0 of the next layer among them, where a flattened
        # (layer * num_blocks + sentinel) index would have landed.
        written = np.zeros(got.shape[:3], bool)
        written[:, batch["write_block"][~dropped],
                batch["write_off"][~dropped]] = True
        np.testing.assert_array_equal(got[~written], before[~written],
                                      err_msg=name)
        assert not written[:, 0].any()        # no entry writes block 0
        assert (got[written] != before[written]).any(), name


def _temp_bytes(lowered):
    analysis = lowered.compile().memory_analysis()
    if analysis is None or not hasattr(analysis, "temp_size_in_bytes"):
        pytest.skip("this backend gives no memory_analysis")
    return analysis.temp_size_in_bytes


def _pool_bytes(pool):
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(pool))


@pytest.mark.parametrize("program", ["fused_step", "draft_propose_scan"])
def test_compiled_step_keeps_no_second_pool(program):
    """The pool is donated and carried: the program's temporaries are a
    fraction of it. With the pool as the scan's xs and ys they held a
    whole second pool and a layer's slice (6.5 MB beside 4.2 MB)."""
    c = get_config("tiny-test")
    params = jax.eval_shape(lambda: tf.init_params(c, jax.random.PRNGKey(0)))
    pool = jax.eval_shape(lambda: init_paged_pool(c, 2048, 16))
    rows, width, entries = 8, 8, 16
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "fused_step":
        lowered = eng._paged_fused_step.lower(
            params, c, i32(6, entries), i32(rows, width), pool,
            jax.ShapeDtypeStruct((2,), jnp.uint32), i32(rows),
            SampleParams(), False)
    else:
        lowered = eng._draft_propose_scan.lower(
            params, c, i32(rows), i32(rows),
            jax.ShapeDtypeStruct((rows,), jnp.bool_), i32(rows, width),
            pool, 3, False)
    assert _temp_bytes(lowered) < _pool_bytes(pool) / 4


# ---- the kernel path, compiled for the chip it runs on -------------------

@pytest.fixture(scope="module")
def one_v5e():
    """One device of a described v5e 2x2: the TPU compiler with no chip.
    Made inside the fixture, so importing this file loads no libtpu."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _benchmark_config(name):
    """A configuration of the benchmark, from its own file."""
    from benchmark import manifest
    return manifest.model_config(manifest.load_json(
        manifest.HERE, "configs", f"{name}.json"))


@pytest.mark.parametrize("model,layers,entries,block", [
    ("qwen2.5-coder-1.5b", None, 48, None),
    ("qwen2.5-coder-1.5b", None, 192, None),
    # other head shapes, cut to four layers (a scan: the program is the
    # same) so the weights fit the described chip: 32/8 and 32/32 x 128
    ("qwen3-8b", 4, 48, None), ("deepseek-coder-6.7b", 4, 192, None),
    # the latent pool: 20 heads over one leaf of rows 640 wide, rows of
    # 4096 tokens, the dense layer and two expert layers
    ("glm-4.7-flash", 3, 48, None), ("glm-4.7-flash", 3, 192, None),
    # the same pool under 32 heads and a 4-row residual stream: both dense
    # layers and two expert layers
    ("xing4.0-29b-a4b", 4, 48, None), ("xing4.0-29b-a4b", 4, 192, None),
    # an explicit block of 16, the cells' size until PR 33: tables 64 and
    # 256 wide
    ("qwen2.5-coder-1.5b", None, 48, 16), ("glm-4.7-flash", 3, 48, 16),
    # 64 heads over the same latent rows, two attention sublayers a layer
    # (four pool layers for two layers), 16 of 512 experts held
    ("longcat-flash-chat", 2, 48, None), ("longcat-flash-chat", 2, 192, None)])
def test_kernel_step_compiled_for_v5e_copies_no_pool(
        one_v5e, model, layers, entries, block, monkeypatch):
    """``_paged_fused_step`` at a preset's widths, 48 rows of 1024 tokens
    (the latent cells: 4096), bf16, the block the engine resolves for
    the pool (``paged_kv.resolve_block_size``: 128 tokens and a table 8
    wide for qwen, 64 and 64 for the latent cells, 32 and 32 at 8 kv
    heads, 16 and 64 at 32) unless one is given, with ``paged_attention_rows`` (a latent
    pool: ``paged_latent_attention_rows``) compiled by Mosaic (the test
    says "on a TPU": the backend here is the CPU), the table whole in
    SMEM. The qwen, glm, xing and longcat cases are the benchmark cells'
    shapes."""
    from senweaver_ide_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    latent = model in ("glm-4.7-flash", "xing4.0-29b-a4b",
                       "longcat-flash-chat")
    c = _benchmark_config(model) if latent else get_config(model)
    if layers:
        c = dataclasses.replace(c, num_layers=layers)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(c, jax.random.PRNGKey(0))))
    max_len = 4096 if latent else 1024
    bs = block or resolve_block_size(kv_row_bytes(c), max_len)
    if block is None:
        assert bs == {"qwen2.5-coder-1.5b": 128, "qwen3-8b": 32,
                      "glm-4.7-flash": 64, "xing4.0-29b-a4b": 64,
                      "longcat-flash-chat": 64}.get(model, 16)
    rows, width = 48, max_len // bs
    pool = on_chip(jax.eval_shape(lambda: init_paged_pool(
        c, 52 * width if layers is None or latent else 13 * width, bs)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_v5e)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = eng._paged_fused_step.lower(
            params, c, i32(6, entries), i32(rows, width), pool,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_v5e),
            i32(rows), SampleParams(temperature=1.0), None).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    leaf = ",".join(map(str, pool.k.shape))
    layer_bytes = pool.k.size * pool.k.dtype.itemsize // pool.k.shape[0]
    # the sampler's (head entries, vocabulary) f32 logits are the step's
    # largest temporary, and a step wider than the rows pays the head for
    # the rows' samplers alone (PR 40: no value is (192, vocabulary)); a
    # layer's slice of one leaf is 27 MB at qwen's widths
    logits_bytes = min(entries, rows) * c.vocab_size * 4
    assert (compiled.memory_analysis().temp_size_in_bytes
            < logits_bytes + layer_bytes / 2)
    if entries > rows:
        assert f"[{entries},{c.vocab_size}]" not in text
        assert f"[{rows},{c.vocab_size}]" in text
    name = "paged_latent_attention_rows" if latent else "paged_attention_rows"
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and f"%{name}." in line]
    assert calls, "the kernel is not in the compiled step"
    # the whole stacked leaf is the kernel's operand: twice (k, v); the
    # latent pool's one leaf once, as the kernel takes it (a lone head is
    # no axis of a block)
    operand = leaf
    if latent:
        operand = ",".join(map(str, pool.k.shape[:3] + pool.k.shape[4:]))
    for line in calls:
        assert line.count(f"bf16[{operand}]") == (1 if latent else 2), line
    for line in text.splitlines():
        for shape in {leaf, operand}:
            if f"bf16[{shape}]" in line.split("=")[0] or \
                    f"= bf16[{shape}]" in line:
                assert (" copy(" not in line
                        and "copy-start" not in line), line
    # nothing of the gather's size is left: (entries x width, block, Hkv,
    # Dh), the latent rows' (entries x width, block, row) and the scores
    # over them
    assert (f"bf16[{entries * width},{bs},{c.num_kv_heads},{c.head_dim}]"
            not in text)
    if latent:
        assert (f"bf16[{entries * width},{bs},{c.latent_row_dim}]"
                not in text)
        assert f"f32[{entries},{c.num_heads},{width * bs}]" not in text


@pytest.mark.parametrize("entries", [48, 192])
def test_hybrid_step_compiled_for_v5e_copies_no_pool_and_no_state(
        one_v5e, entries, monkeypatch):
    """``_paged_fused_step`` at Falcon-H1-34B's widths (two of its layers;
    the falcon cell's shapes: 48 rows of 4096 tokens in the blocks of 64
    the engine resolves for 20/4 heads, 56 state rows), compiled
    for the v5e: attention at 20/4 heads goes through ``paged_attention_rows``
    (Mosaic compiles it; no gather), and the rows' float32 state — 4 MB a
    row a layer — is updated where it lies: no copy of the stacked leaf or
    of a step's 48-row slab, no temporary of a slab's size."""
    from senweaver_ide_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    c = dataclasses.replace(_benchmark_config("falcon-h1-34b-instruct"),
                            num_layers=2)
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(c, jax.random.PRNGKey(0))))
    bs = resolve_block_size(kv_row_bytes(c), 4096)
    assert bs == 64
    rows, width = 48, 4096 // bs
    pool = on_chip(jax.eval_shape(lambda: init_paged_pool(
        c, 52 * width, bs, state_rows=56)))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_v5e)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = eng._paged_fused_step.lower(
            params, c, i32(6, entries), i32(rows, width), pool,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_v5e),
            i32(rows), SampleParams(temperature=1.0), None).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    slab_bytes = rows * 32 * 128 * 256 * 4
    assert (compiled.memory_analysis().temp_size_in_bytes
            < entries * c.vocab_size * 4 + slab_bytes / 2)
    if entries > rows:      # the head runs over the rows' samplers (PR 40)
        assert f"[{entries},{c.vocab_size}]" not in text
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "%paged_attention_rows." in line]
    assert calls, "the kernel is not in the compiled step"
    # the decode rows' states in one pass (ops/state_step.py), once a
    # layer in the scan's body: the leaf aliased, no copy of it below
    assert any("tpu_custom_call" in line and "%state_step_mamba2" in line
               for line in text.splitlines())
    assert f"bf16[{entries * width},{bs},4,128]" not in text
    for shape in ("f32[2,56,32,128,256]", "f32[48,32,128,256]",
                  "f32[1,48,32,128,256]",
                  f"bf16[2,{52 * width},{bs},4,128]"):
        for line in text.splitlines():
            if f"= {shape}" in line:
                assert (" copy(" not in line
                        and "copy-start" not in line), line


@pytest.mark.parametrize("entries", [48, 192])
def test_layer_pattern_step_compiled_for_v5e_copies_no_pool_ring_or_state(
        one_v5e, entries, monkeypatch):
    """``_paged_fused_step`` at Phi-4-mini-flash-reasoning's widths (all
    32 layers: three scans of unlike layers; the phi4 cell's shapes: 48
    rows of 4096 tokens in blocks of 32, 56 state rows, rings of 704
    positions), compiled for the v5e. The ten cache rows a token are stored
    folded 5 x 2, which XLA:TPU tiles ``T(2,128)`` without padding (an axis
    of 10 it pads to 16 and Mosaic cannot cut a block out of it); the full
    layer's blocks and the window layers' rings both go through
    ``paged_attention_rows``; the rings are read as blocks by a reshape that
    moves nothing; no leaf of the pool is copied, and nothing of a gather's
    size is left."""
    from senweaver_ide_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    c = _benchmark_config("phi-4-mini-flash-reasoning")
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(c, jax.random.PRNGKey(0))))
    bs = resolve_block_size(kv_row_bytes(c), 4096)
    assert bs == 32
    rows, width = 48, 4096 // bs
    pool = on_chip(jax.eval_shape(lambda: init_paged_pool(
        c, 52 * width, bs, state_rows=56, step_tokens=192)))
    blocks, ring = "1,6656,160,2,128", "8,56,3520,2,128"
    assert ",".join(map(str, pool.k.shape)) == blocks
    assert ",".join(map(str, pool.rows.win_k.shape)) == ring
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_v5e)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = eng._paged_fused_step.lower(
            params, c, i32(6, entries), i32(rows, width), pool,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_v5e),
            i32(rows), SampleParams(temperature=1.0), None).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    # the logits and little else: no second pool, ring or state
    assert (compiled.memory_analysis().temp_size_in_bytes
            < 2 * entries * c.vocab_size * 4)
    if entries > rows:      # the head runs over the rows' samplers (PR 40)
        assert f"[{entries},{c.vocab_size}]" not in text
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "%paged_attention_rows." in line]
    assert len(calls) >= 3, "full, window and cross layers run the kernel"
    # a call's result: the entries + a tile of 10 queries, 40 heads in 48
    # rows. The layers that write attend every entry; the cross layers, a
    # wide step's 48 gathered samplers alone (PR 46)
    widths = sorted({int(n) for line in calls for n in re.findall(
        r"= bf16\[(\d+),48,128\]", line)})
    assert widths == sorted({entries + 10, rows + 10}), widths
    assert f"bf16[{blocks}]{{4,3,2,1,0:T(2,128)(2,1)}}" in text
    assert f"bf16[{entries * width},{bs},10,128]" not in text
    for shape in (f"bf16[{blocks}]", f"bf16[{ring}]",
                  "bf16[8,1232,160,2,128]", "f32[9,56,16,5120]",
                  "f32[48,16,5120]"):
        for line in text.splitlines():
            if f"= {shape}" in line:
                assert (" copy(" not in line
                        and "copy-start" not in line), line


@pytest.mark.parametrize("entries", [192])    # decode pass and chunk loop
def test_delta_rule_step_compiled_for_v5e_copies_no_pool_and_no_state(
        one_v5e, entries, monkeypatch):
    """``_paged_fused_step`` at Solar-Open2-250B's widths (the cell's one
    period: a gated GQA layer 64/8 x 128 and three delta-rule layers over
    40 held experts; 48 rows of 4096 tokens in blocks of 32, 56 state rows
    of 64 x 128 x 128 float32), compiled for the v5e: the GQA layer runs
    ``paged_attention_rows``, no leaf of the pool is copied, the decode
    rows' pass (``state_step_delta``, the state leaf aliased) leaves no
    temporary of a 48-row slab of states (201 MB), and
    a chunk's ``(2C, C, H, K)`` decays (268 MB) are consumed where they are
    made."""
    from senweaver_ide_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "on_tpu", lambda: True)
    c = _benchmark_config("solar-open2-250b")
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_v5e),
        tree)
    params = on_chip(jax.eval_shape(
        lambda: tf.init_params(c, jax.random.PRNGKey(0))))
    bs = resolve_block_size(kv_row_bytes(c), 4096)
    assert bs == 32
    rows, width = 48, 4096 // bs
    pool = on_chip(jax.eval_shape(lambda: init_paged_pool(
        c, 52 * width, bs, state_rows=56, step_tokens=192)))
    blocks, state = "1,6656,32,8,128", "3,56,64,128,128"
    assert ",".join(map(str, pool.k.shape)) == blocks
    assert ",".join(map(str, pool.rows.ssm.shape)) == state
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_v5e)
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = eng._paged_fused_step.lower(
            params, c, i32(6, entries), i32(rows, width), pool,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_v5e),
            i32(rows), SampleParams(temperature=1.0), None).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    text = compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
    assert any("tpu_custom_call" in line and "%paged_attention_rows." in line
               for line in text.splitlines())
    # the three delta-rule layers' decode pass through the one-pass kernel
    # (ops/state_step.py): the leaf aliased in and out, no copy beside it
    assert sum("tpu_custom_call" in line and "%state_step_delta" in line
               for line in text.splitlines()) == 3
    assert f"bf16[{entries * width},{bs},8,128]" not in text
    # (the 25 MB of conv windows are prefetched whole into fast memory,
    # ``copy-start`` to ``S(1)``: not a second pool, and not listed)
    for shape in (f"bf16[{blocks}]", f"f32[{state}]",
                  "f32[48,64,128,128]"):
        for line in text.splitlines():
            if f"= {shape}" in line:
                assert (" copy(" not in line
                        and "copy-start" not in line), line


def _preset_head_shapes():
    """Every (Hq, Hkv, Dh) a full-size dense preset has, once, under the
    first preset's name."""
    from senweaver_ide_tpu.models.config import PRESETS
    shapes = {}
    for name in sorted(PRESETS):
        c = PRESETS[name]()
        if not c.mla and "test" not in name:
            shapes.setdefault((c.num_heads, c.num_kv_heads, c.head_dim),
                              name)
    return [pytest.param(name, id=f"{name}-{hq}-{hkv}x{d}")
            for (hq, hkv, d), name in shapes.items()]


@pytest.mark.parametrize("model", _preset_head_shapes())
def test_every_preset_takes_a_path_that_compiles_for_v5e(one_v5e, model,
                                                         monkeypatch):
    """On a TPU ``forward_paged`` sends a dense unquantized pool to the
    kernel only where Mosaic compiles it: at each preset's head shape the
    kernel alone, over a table as wide as the engine hands it (a row of
    1024 tokens in blocks of 16 and in the blocks the engine resolves for
    these heads: the kernel's table keeps ``blocks_per_row``), at a narrow
    and a wide step's entries, with the pool's leaves not copied on the
    way in. A ``head_dim`` of 64 keeps the gather."""
    from senweaver_ide_tpu.ops import paged_attention as pa
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    c = get_config(model)
    # what a token's cache row is: a differential-attention model's 40/20
    # heads of 64 are 10 rows of 128 (``ModelConfig.cache_kv_heads``), and
    # a head axis of 10 is stored folded 5 x 2 (``stored_kv_heads``)
    hq, hkv, d = c.num_heads, c.cache_kv_heads, c.cache_head_dim
    if d % 128:
        assert not tf.reads_pool_in_place(c, None)
        return
    assert tf.reads_pool_in_place(c, None)
    rows, layers = 48, 2
    fold = hkv // stored_kv_heads(hkv)
    struct = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                       sharding=one_v5e)

    def attend(q, k_leaf, v_leaf, tables, seq_row, positions):
        plan = pa.plan_rows(seq_row, positions,
                            block_size=k_leaf.shape[2] // fold,
                            table_width=tables.shape[1],
                            q_tile=pa.query_tile(hq))
        return pa.paged_attention_rows(q, k_leaf, v_leaf, jnp.int32(1),
                                       tables, positions, plan,
                                       kv_heads=hkv if fold > 1 else None)

    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for bs, entries in sorted(
                {(b, e) for b in (16, resolve_block_size(kv_row_bytes(c),
                                                         1024))
                 for e in (48, 192)}):
            width = 1024 // bs
            leaf = struct((layers, 13 * width, bs * fold, hkv // fold, d),
                          jnp.bfloat16)
            text = jax.jit(attend).lower(
                struct((entries, hq, d), jnp.bfloat16), leaf, leaf,
                struct((rows, width), jnp.int32),
                struct((entries,), jnp.int32),
                struct((entries,), jnp.int32)).compile().as_text()
            assert "paged_attention_rows" in text
            shape = ",".join(map(str, leaf.shape))
            assert not [line for line in text.splitlines()
                        if f"= bf16[{shape}]" in line and " copy(" in line]
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
