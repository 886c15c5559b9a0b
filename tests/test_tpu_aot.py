"""Compile the device path for a TPU v5e from this CPU-only host.

The installed libtpu describes a v5e host with no chip attached, and
`jit(f).lower(<ShapeDtypeStruct placed on those devices>).compile()` then
runs the real XLA:TPU and Mosaic compilers. The CPU tests take the dense
attention branch and interpret the kernels, so without this nothing in
tier-1 sees what the chip's compiler sees — e.g. "Mosaic kernels cannot be
automatically partitioned" when a bare pallas_call meets a sharded mesh.
Nothing is executed; `chip_smoke.py` is the check that the compiled code is
also right.
"""

import dataclasses
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama, model_of, moe, ouro
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.parallel import sharding as shd
from ray_tpu.parallel.mesh import make_mesh
from ray_tpu.train import spmd

MOSAIC = "tpu_custom_call"  # the custom-call target of a compiled Pallas kernel


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu here: nothing to compile with
        pytest.skip(f"no TPU topology description available: {e!r}")
    assert len(topo.devices) == 4 and topo.devices[0].platform == "tpu"
    # make_train_step turns the persistent compile cache on for a TPU mesh;
    # the CPU tests that run after this module should not inherit it
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = jax.config.jax_compilation_cache_dir
    yield topo.devices
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    compilation_cache.reset_cache()


def _on(dev, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(dev))


@pytest.mark.parametrize("D", [64, 128])
def test_flash_fwd_bwd_compiles(v5e, D):
    q = _on(v5e[0], (1, 1024, 8, D))
    kv = _on(v5e[0], (1, 1024, 2, D))

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert text.count(MOSAIC) >= 3  # forward, dQ, dK/dV


@pytest.mark.parametrize("S, Hq, Hkv, D, Dv", [(2048, 64, 64, 192, 128), (1024, 8, 2, 96, 64)],
                         ids=["kimi-2048", "grouped-96/64"])
def test_flash_forward_compiles_at_two_widths(v5e, S, Hq, Hkv, D, Dv):
    """The forward with q/k of one width beside v of another, at the Kimi
    cell's fresh prefill (64 heads of 192 = 128 + 64 rotary beside 128, the
    2,048 bucket, the family's scale) and at a grouped shape: Mosaic takes a
    width that is no whole number of 128-lane tiles as the array's full last
    dimension (no zero lanes carried to 256), ONE kernel under its own name,
    the output Dv wide; the kernel's tiles are the ones a single width gets."""
    from ray_tpu.ops.flash_attention import choose_tiles

    q, k, v = (_on(v5e[0], (1, S, h, d)) for h, d in ((Hq, D), (Hkv, D), (Hkv, Dv)))
    attend = functools.partial(flash_attention, interpret=False, scale=192 ** -0.5 * 2.0)
    text = jax.jit(attend).lower(q, k, v).compile().as_text()
    kernels = _kernel_lines(text)
    assert len(kernels) == 1 and re.match(r"(ROOT )?%flash_attention_fwd", kernels[0]), kernels
    assert re.search(rf"ENTRY .*-> bf16\[1,{S},{Hq},{Dv}\]", text)
    assert f"bf16[{Hq},{S},{D}]" in kernels[0] and "256]" not in kernels[0]
    assert choose_tiles(S, D, 2, "fwd", Dv) == choose_tiles(S, 128, 2, "fwd") == (1024, 1024)


def _kernel_lines(text: str) -> list[str]:
    return [ln.lstrip() for ln in text.splitlines() if MOSAIC in ln]


def _group_broadcasts(text: str, n_q_elems: int) -> list[str]:
    """Instructions that broadcast an ARRAY (not a scalar) to at least q's
    size: what `jnp.repeat` of K or V over the query group compiles to
    (`bf16[B,S,Hkv,g,D] broadcast(...), dimensions={0,1,2,4}`)."""
    out = []
    for ln in text.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* broadcast\(", ln)
        if (m and "dimensions={}" not in ln
                and math.prod(map(int, m.group(1).split(","))) >= n_q_elems):
            out.append(ln.strip()[:160])
    return out


def _assert_flash_at_cell_shape(text: str, n_q_elems: int):
    kernels = _kernel_lines(text)
    assert len(kernels) >= 3, kernels                     # forward, dQ, dK/dV
    # outside the model's remat an instruction is named after its transform
    # too (`%transpose_jvp_flash_attention_dq__`); inside it, the test below
    # holds the names to what a profile of the train step shows
    assert all(re.match(r"(ROOT )?%(\w*jvp_)?flash_attention_(fwd|dq|dkv)", ln)
               for ln in kernels), kernels
    assert not _group_broadcasts(text, n_q_elems)


def test_group_broadcast_detector_sees_a_repeat(v5e):
    """The detector is not vacuous: K repeated over the group, the layout this
    kernel no longer needs, is found in the compiled text."""
    kv = _on(v5e[0], (1, 1024, 2, 128))
    text = jax.jit(lambda k: jnp.repeat(k, 4, axis=2) * 2).lower(kv).compile().as_text()
    assert _group_broadcasts(text, 1024 * 8 * 128)


def test_flash_compiles_at_the_one_chip_cell_shape(v5e):
    """`train-4k-1chip`'s attention, [3, 4096, 32, 128] with 8 KV heads, with
    the tiles the kernel works out itself: fits VMEM, keeps its names, and
    reads K and V by group (no [B, S, Hq, D]-sized copy of either)."""
    B, S, Hq, Hkv, D = 3, 4096, 32, 8, 128
    q, kv = _on(v5e[0], (B, S, Hq, D)), _on(v5e[0], (B, S, Hkv, D))

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    _assert_flash_at_cell_shape(text, B * S * Hq * D)


def test_flash_compiles_at_the_fsdp4_cell_shape(v5e):
    """`train-4k-fsdp4`'s attention: 8 sequences over fsdp=4, so [2, 4096, 32,
    128] a device, under `default_attn_fn`'s shard_map."""
    B, S, Hq, Hkv, D = 8, 4096, 32, 8, 128
    mesh = make_mesh(4, fsdp=4, devices=v5e)
    attn = spmd.default_attn_fn(mesh)
    sh = lambda *names: jax.sharding.NamedSharding(mesh, shd.spec_from_logical(names))
    q = jax.ShapeDtypeStruct((B, S, Hq, D), jnp.bfloat16,
                             sharding=sh("batch", None, "heads", None))
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16,
                              sharding=sh("batch", None, "kv_heads", None))

    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    _assert_flash_at_cell_shape(text, B // 4 * S * Hq * D)
    assert f"bf16[{B // 4 * Hq},{S},{D}]" in text        # a device's own shard


@pytest.mark.parametrize("B, Hq, Hkv, D, max_blocks, pool_blocks", [
    # a 64-wide head sits in a pool allocated 128 wide (`llama.init_kv_pool`)
    (8, 32, 8, 64, 16, 129), (8, 32, 8, 128, 16, 129),
    # `serve-chat-steady` and `serve-docs-batch`: 32 slots, a 128-block table
    # over the engine's 4,097-block pool; a VMEM or Mosaic limit at the real
    # size fails here and not on the chip
    (32, 32, 8, 128, 128, 4097),
    # OLMoE's attention at the same engine sizes: 16 KV heads, one query head each
    (32, 16, 16, 128, 128, 4097),
], ids=["D64", "D128", "serving-cells", "olmoe-heads"])
def test_paged_decode_compiles(v5e, B, Hq, Hkv, D, max_blocks, pool_blocks):
    BS, L = 16, 2
    d = v5e[0]
    pool = _on(d, (L, pool_blocks, BS, Hkv * llama.pool_head_dim(D)))
    text = jax.jit(
        lambda *a: paged_decode_attention(*a[:-1], layer=a[-1], interpret=False)).lower(
        _on(d, (B, Hq, D)), pool, pool, _on(d, (B, max_blocks), jnp.int32),
        _on(d, (B,), jnp.int32), _on(d, (), jnp.int32)).compile().as_text()
    assert MOSAIC in text
    assert "paged_attention_decode" in text


def _placed_model(d, cfg, pool_blocks: int, bs: int):
    """(the family's `Model`, its weights' shapes on device d, its pool's
    shapes there, the pool's bare shapes): what a step is lowered with."""
    model = model_of(cfg)
    place = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    pool = jax.eval_shape(lambda: model.init_kv_pool(cfg, pool_blocks, bs))
    params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
    return model, place(params), place(pool), pool


def _paged_step(d, cfg, *, B, S, bs=16, max_blocks=8, pool_blocks=257, donate=True):
    """One `forward_paged` step told it runs on a TPU, lowered for device d
    with the pool donated, as the paged engines' steps are (`paged_step`):
    B = 1 and S > 1 is a prefill, S = 1 the decode step. The forward, the
    pool and the weights are those of `cfg`'s family (`model_of`)."""
    model, params, pool, _ = _placed_model(d, cfg, pool_blocks, bs)

    def step(params, pool, tokens, tables, lengths):
        return model.forward_paged(params, tokens, cfg, pool, tables, lengths,
                                   bs, platform="tpu")

    return jax.jit(step, donate_argnums=(1,) if donate else ()).lower(
        params, pool, _on(d, (B, S), jnp.int32),
        _on(d, (B, max_blocks), jnp.int32), _on(d, (B,), jnp.int32))


def _decode_step_text(d) -> str:
    """The compiled HLO of one paged decode step told it runs on a TPU."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.bfloat16,
                              head_dim=64)
    return _paged_step(d, cfg, B=4, S=1, pool_blocks=33).compile().as_text()


def pool_sized_instructions(text: str, pool_shape: tuple) -> list[tuple[str, str]]:
    """(opcode, line) of every instruction of a compiled program, fused
    computations included, that produces an ARRAY with as many elements as the
    K (or V) pool or as one layer of it, whatever shape XLA gave it (a step's
    scatter sees the pool as [L * NB * BS, Hkv * Dp]). A fusion counts under
    the opcode of its root."""
    sizes = {math.prod(pool_shape), math.prod(pool_shape[1:])}
    instr = re.compile(r"\s*(ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(")
    roots, computation = {}, None
    for ln in text.splitlines():
        if m := re.match(r"(?:ENTRY )?%(\S+) \(.*\) -> .* \{$", ln):
            computation = m.group(1)
        elif (m := instr.match(ln)) and m.group(1):
            roots[computation] = m.group(3)
    out = []
    for ln in text.splitlines():
        m = instr.match(ln)
        if m and math.prod(map(int, m.group(2).split(","))) in sizes:
            called = re.search(r"calls=%([^\s,)]+)", ln)
            op = roots.get(called.group(1), "fusion") if called else m.group(3)
            out.append((op, ln.strip()))
    return out


def weight_relayouts(text: str, params) -> list[tuple[str, str]]:
    """(opcode, line) of every instruction of a compiled program, fused
    computations included, that re-lays out or stages a layer's WEIGHT: a
    `copy` or `transpose` wherever it lands, or a `dynamic-slice` or `fusion`
    placed in VMEM (`S(1)` in its layout), whose result has the dimensions
    (in any order, ones dropped) of a whole parameter stack or of one layer's
    slice of it. A stack is a dict-valued entry of `params` (`layers`,
    `lead_layers`, a family's stacks by kind), a weight a leaf of it whose
    layer's slice has 2^20 elements or more (2 MB: LFM2's `wk`; under that a
    weight's dimensions are a step's rows' too, LFM2's router `[2048, 32]`
    beside `bf16[32, 2048]`, and staging it costs a microsecond). What it finds
    in a decode step built without `llama.project_heads`' barrier: the
    scan's slice of `wq` staged in VMEM and transposed there a layer, Ouro's
    whole `wq` and `wk` stacks re-laid out a step (PERF.md section 6, PR 46).
    NOT counted: `copy-start` / `copy-done`, `slice-start` / `slice-done` and
    their `ConcatBitcast`, the memory-space assignment's asynchronous
    prefetch of a small stack into VMEM (Kimi's one leading layer, LFM2's and
    Nemotron's stacks of six): the same bytes read from HBM once, under
    other work, which a product then reads in place."""
    key = lambda shape: tuple(sorted(d for d in shape if d != 1))
    stacks = [v for v in params.values() if isinstance(v, dict)]
    weights = {key(shape) for stack in stacks for leaf in jax.tree.leaves(stack)
               if math.prod(leaf.shape[1:]) >= 2 ** 20 for shape in (leaf.shape, leaf.shape[1:])}
    instr = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\](\S*) ([\w-]+)\(")
    out = []
    for ln in text.splitlines():
        m = instr.match(ln)
        if not m or key(map(int, m.group(1).split(","))) not in weights:
            continue
        op, in_vmem = m.group(3), "S(1)" in m.group(2)
        if op in ("copy", "transpose") or (in_vmem and op in ("dynamic-slice", "fusion")):
            out.append((op, ln.strip()[:200]))
    return out


def array_lines(text: str, shape: tuple) -> list[str]:
    """The instructions of a compiled text that produce an array of `shape`
    (any dtype), bitcasts and fused ones too."""
    dims = ",".join(map(str, shape))
    return [ln.strip()[:160] for ln in text.splitlines()
            if re.match(rf"\s*(?:ROOT )?%\S+ = \w+\[{dims}\]", ln)]


def assert_a_share_moves_its_bound(text: str, cfg, tokens: int, compacts: bool) -> None:
    """A compiled step of `tokens` rows whose expert layers hold a share:
    where `moe.held_rows_trip` is under the T x k pairs no instruction
    produces an array of T x k rows of the hidden width (the layers gather
    an even share at a time, PR 38 and PR 47); where it reaches them the step
    has such arrays, as it had."""
    experts = cfg.experts
    pairs = tokens * experts.top_k
    assert (moe.held_rows_trip(pairs, experts.experts_held[1], experts.num_experts)
            < pairs) == compacts
    assert bool(array_lines(text, (pairs, cfg.base.hidden_size))) == (not compacts)


def pool_scatter_updates(text: str, leaf_shape: tuple) -> list[tuple]:
    """The shape of the UPDATES operand of every scatter of a compiled text
    that produces an array the size of the pool leaf `leaf_shape`, in whatever
    view XLA took of it: `(2048, 1024)` is 2,048 rows of a token each (the
    row scatter sees the pool as `[L * NB * BS, row]`), `(128, 16, 1024)` 128
    whole pages."""
    dims = lambda s: tuple(int(d) for d in s.split(",") if d)
    shape_of = {name: dims(d) for name, d in re.findall(r"%(\S+) = \w+\[([\d,]*)\]", text)}
    return [shape_of[m.group(2)] for m in re.finditer(
        r"= \w+\[([\d,]+)\]\S* scatter\(%\S+, %\S+, %([^\s,)]+)\)", text)
        if math.prod(dims(m.group(1))) == math.prod(leaf_shape)]


def assert_a_fresh_prefill_writes_whole_pages(text: str, leaf_shape: tuple, S: int,
                                              scope: str, leaves: int) -> None:
    """What ISSUE 43 holds a compiled fresh prefill of S rows to: the writes
    of its row-a-token leaves (`leaves` of them, each `leaf_shape`) are
    scatters of S / block_size whole `[block_size, row]` pages under `scope`,
    and NO scatter of S updates is left (XLA:TPU walks a scatter's indices one
    at a time: 2,048 rows of 1,024 lanes took 0.27 ms a leaf and layer, their
    128 pages take 0.03; PERF.md section 6, PR 43). The scatter keeps the
    pool's own four dimensions and its `op_name`, which the row scatter's
    fusion lost."""
    bs, row = leaf_shape[2:]
    updates = pool_scatter_updates(text, leaf_shape)
    assert updates and set(updates) == {(S // bs, bs, row)}, updates
    assert len(updates) >= leaves
    assert f"{scope}/scatter" in text


def assert_pool_stays_in_place(compiled, pool, scratch_under: int | None = None,
                               alloc_under: int = 0, writes: tuple = ("scatter",)) -> None:
    """What ISSUE 30 holds a paged step to. `pool` is the pool's shapes (K and
    V, a latent family's one leaf, or leaves of several shapes: `lfm2`'s `conv`
    beside its `k` and `v`); `scratch_under` bounds the step's scratch
    (default: one layer's pages of the largest leaf). No buffer is allocated
    inside the program, but those a caller names with `alloc_under`, the bytes
    every one of them stays under. `writes` are the opcodes a write into the
    pool compiles to: the scatter, and for a family that writes ONE page a
    sequence at B = 1 (`nemotron_h`'s state) also `dynamic-update-slice`."""
    text = compiled.as_text()
    pages = [leaf for name, leaf in pool.items() if name != "counters"]
    layer_bytes = max(math.prod(page.shape[1:]) * page.dtype.itemsize for page in pages)
    # the donation is honoured: every page-shaped leaf aliases an output
    aliases = re.search(r"input_output_alias=\{(.*?)\}, entry", text)
    assert aliases and len(re.findall(r"(may|must)-alias", aliases.group(1))) >= len(pages)
    if not alloc_under:
        assert "AllocateBuffer" not in text
    for ln in text.splitlines():
        if "AllocateBuffer" in ln:
            dtype, dims = re.search(r"= (\w+)\[([\d,]*)\]", ln).groups()
            elems = math.prod(map(int, filter(None, dims.split(","))))
            width = int(re.sub(r"\D", "", dtype) or 8) // 8   # bf16: 2, s32: 4
            assert elems * width < alloc_under, ln[:200]
    # parameters, tuple elements and bitcasts move nothing; every other
    # producer of a pool is a write into it, and the only one a step has is
    # the scatter of the model's own `kv_write`, of rows or of whole pages
    for shape in {page.shape for page in pages}:
        moved = [(op, ln[:200]) for op, ln in pool_sized_instructions(text, shape)
                 if op not in ("parameter", "get-tuple-element", "bitcast", *writes)]
        assert not moved, moved
    # nothing the size of a layer's pages is scratch either
    assert compiled.memory_analysis().temp_size_in_bytes < (scratch_under or layer_bytes)


def _compiled_pool_step(d, **step):
    """(compiled step, the pool's shapes) at the cells' structure: 16-wide
    blocks, a table, a pool of 257 blocks, 3 layers, 128-wide heads."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.bfloat16, hidden_size=512,
        intermediate_size=1024, num_heads=4, num_kv_heads=2, head_dim=128,
        num_layers=3, vocab_size=512)
    return (_paged_step(d, cfg, **step).compile(),
            jax.eval_shape(lambda: llama.init_kv_pool(cfg, 257, 16)))


@pytest.mark.parametrize("B, S", [(8, 1), (1, 128)], ids=["decode", "prefill"])
def test_paged_step_leaves_the_pool_where_it_is(v5e, B, S):
    """The paged decode step and a B = 1 prefill with the pool donated: the
    pool is carried through the layer scan and written in place, so the
    compiled program has no second pool, no copy or re-layout of the pool or
    of one layer of it, and scratch under one layer's pages. The scan that
    sliced and restacked a [L, Hkv, NB, BS, D] pool had 22 such instructions
    and 5.37 GB of scratch at the cells' size, and the same pool carried
    head-major was copied whole in every layer (PERF.md section 6, PR 30)."""
    assert_pool_stays_in_place(*_compiled_pool_step(v5e[0], B=B, S=S))


@pytest.mark.parametrize("B, S, scratch_under", [(32, 1, 2 ** 20), (1, 256, 2 ** 30)],
                         ids=["decode", "prefill-256"])
def test_ouro_paged_step_at_its_published_widths(v5e, B, S, scratch_under):
    """`serve-ouro-shortin-batch`'s two largest programs at Ouro-2.6B's
    published widths, whole: 48 layers run 4 times, 16 heads of 128, 32 slots,
    a 128-block table, the 321-block pool `bf16[192, 321, 16, 2048]` (8.08 GB
    for keys and values) donated, beside 5.34 GB of weights. They compile for
    a v5e (an out-of-HBM or Mosaic refusal fails here, not on the chip), the
    pool carried through TWO nested scans (passes, layers) stays in place (the
    only pool-shaped instructions are the `kv_write` scatters), and the text
    names the scopes a profile is read by. The PREFILL's scratch is 806 MB,
    none of it the pool's: XLA hoists a re-layout of the whole stacked `wq`
    and `wk` (`bf16[48, 2048, 2048]`, 403 MB each) out of both loops, once an
    admission (PERF.md section 6, PR 31); the decode step did the same once a
    STEP until its projections held their results (`llama.project_heads`, PR
    46) and has 0.4 MB since."""
    cfg = dataclasses.replace(ouro.OuroConfig.ouro_2_6b(), max_seq_len=2048)
    compiled = _paged_step(v5e[0], cfg, B=B, S=S, max_blocks=128,
                           pool_blocks=321).compile()
    pool = jax.eval_shape(lambda: ouro.init_kv_pool(cfg, 321, 16))
    assert pool["k"].shape == (192, 321, 16, 2048)
    assert_pool_stays_in_place(compiled, pool, scratch_under=scratch_under)
    text = compiled.as_text()
    names = ["/loop/", "loop/norm", "attn/kv_write", "attn/kv_read"]
    for name in names + (["paged_attention_decode", MOSAIC] if S == 1 else []):
        assert name in text, name


def _kimi_serve_ep32():
    """The model of `kimi-k2.7-code-serve-ep32-1chip`, from the cell's own
    file, and the file's engine section."""
    import json
    import os

    from benchmarks.harness.families import kimi_k2 as family

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "kimi-k2.7-code-serve-ep32-1chip.json")
    with open(path) as f:
        file = json.load(f)
    return family.model_config({k: file[k] for k in family.MODEL_KEYS}), file["engine"]


@pytest.mark.parametrize("name, B, S, kw, scratch_under, alloc_under", [
    # the expert layers' sort allocates its words of scratch: s32[85] and s32[7]
    ("decode", 64, 1, dict(head=0), 0.5e9, 512),
    # and the prefill's map over 4 chunks of 16 heads its stacked output,
    # bf16[4, 1, 2048, 16, 128]: ONE attention output of 33.6 MB, a fifth of
    # a layer's pages (167.8 MB). Scratch: 233.5 MB by the compiler, where it
    # was 531.6 MB while the expert layer moved all 16,384 pairs (PR 38)
    ("prefill", 1, 2048, dict(head="last", table_first=True), 0.3e9,
     2048 * 64 * 128 * 2 + 1),
    # the program the cell runs since PR 41: a span that starts at 0 attends
    # over its own rows through the flash forward, every head in one call, so
    # the stacked output is gone and what is allocated is the sort's words
    ("prefill", 1, 2048, dict(head="last", table_first=True, fresh=True), 0.3e9, 512),
], ids=["decode-64", "prefill-2048", "prefill-2048-fresh"])
def test_kimi_engine_steps_compile_beside_weights_and_pool(v5e, name, B, S, kw, scratch_under,
                                                           alloc_under):
    """`serve-kimi-longin-batch`'s two largest programs as the engine builds
    them, at Kimi-K2.7-Code's published widths and the configuration's cut (1
    dense + 7 expert layers, 12 of 384 experts, 20,480 vocabulary rows): 64
    slots, a 128-block table, the latent pool `bf16[8, 8193, 16, 640]` (1.34
    GB) donated beside 11.05 GB of weights. They compile for a v5e (an
    out-of-HBM or Mosaic refusal fails here, not on the chip); the pool,
    carried through BOTH scans (the leading dense stack, the expert stack),
    stays in place: the only pool-shaped instructions are the `latent_write`
    scatters; the text names the scopes a profile is read by, and at decode
    the latent kernel. The compiler's bytes are what the configuration
    file's depth rule is decided by (`num_blocks_note`). The expert layers
    move the rows they keep (`moe.held_rows_trip`: 768 of the prefill's
    16,384 pairs, 256 of the decode step's 512, a trip at a time): NO
    instruction of either program produces an array of T x k rows of the
    hidden width, where the prefill had three `bf16[16384, 7168]` a layer."""
    cfg, engine = _kimi_serve_ep32()
    assert (engine["max_batch_size"], engine["num_blocks"], engine["block_size"]) == (64, 8193, 16)
    assert 2048 in engine["prefill_buckets"]
    lowered, pool = _engine_step(v5e[0], cfg, name, B=B, S=S,
                                 pool_blocks=engine["num_blocks"], **kw)
    compiled = lowered.compile()
    assert pool["latent"].shape == (8, 8193, 16, 640)
    assert {k: v.shape for k, v in pool["counters"].items()} == {"moe_rows": (), "moe_moved": ()}
    assert_pool_stays_in_place(compiled, pool, scratch_under=int(scratch_under),
                               alloc_under=alloc_under)
    ma = compiled.memory_analysis()
    # weights 11.05 GB + pool 1.34 GB, and the step's scratch: under the 15.0
    # GB of the depth rule with the room a run's reference needs beside it
    assert 12.3e9 < ma.argument_size_in_bytes < 12.5e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13.5e9
    text = compiled.as_text()
    fresh = kw.get("fresh", False)
    names = ["lead/", "attn/latent_write", "moe/route", "moe/dispatch",
             "moe/experts", "moe/combine", "moe/shared", "grouped_matmul_fwd"]
    names += ["attn/prompt_attend", "flash_attention_fwd"] if fresh else ["attn/latent_read"]
    for scope in names + (["attn/absorb", "latent_attention_decode"] if S == 1 else []):
        assert scope in text, scope
    assert ("latent_attention_decode" in text) == (S == 1)
    assert "paged_attention_decode" not in text
    assert_a_share_moves_its_bound(text, cfg, B * S, compacts=True)
    assert_a_fresh_latent_prefill_reads_its_own_rows(text, S, fresh)
    if fresh:
        assert_a_fresh_prefill_writes_whole_pages(text, pool["latent"].shape, S,
                                                  "attn/latent_write", leaves=1)
    else:   # a row a token: the table prefill's 2,048, the decode step's 64
        assert set(pool_scatter_updates(text, pool["latent"].shape)) == {(B * S, 640)}


def assert_a_fresh_latent_prefill_reads_its_own_rows(text: str, S: int, fresh: bool) -> None:
    """A latent family's compiled step of S rows a sequence: told `fresh` it
    has nothing under `attn/latent_read` (no gather of the table) and no
    float32 array of heads x bucket x bucket (from the 1,024 bucket up the
    scores stay in the flash kernel's VMEM; the table program forms
    `f32[1, 16, 2048, 2048]` a chunk of heads and is what the detector finds
    them in; the expert layers' combine weights `[2048, 768]`, T x trip, are
    two-dimensional and in both)."""
    assert ("attn/prompt_attend" in text) == fresh
    assert ("attn/latent_read" in text) == (not fresh)
    assert ("flash_attention_fwd" in text) == (fresh and S >= 1024)
    if S >= 1024:
        scores = [m.group(1) for m in re.finditer(r"= f32\[([\d,]+)\]", text)
                  if m.group(1).count(",") >= 2 and m.group(1).split(",").count(str(S)) >= 2]
        assert bool(scores) == (not fresh), scores[:3]


def _xing4_serve_ep8():
    """The model of `xing4.0-29b-a4b-serve-ep8-1chip`, from the cell's own
    file, and the file's engine section."""
    import json
    import os

    from benchmarks.harness.families import xing4 as family

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "xing4.0-29b-a4b-serve-ep8-1chip.json")
    with open(path) as f:
        file = json.load(f)
    return family.model_config({k: file[k] for k in family.MODEL_KEYS}), file["engine"]


@pytest.mark.parametrize("name, B, S, kw, scratch_under, alloc_under", [
    # the expert layers' sort allocates its words of scratch: s32[304], the
    # rows of each of the 38 x 8 held experts
    ("decode", 48, 1, dict(head=0), 0.3e9, 2048),
    ("prefill", 1, 512, dict(head="last", table_first=True), 0.5e9, 2048),
    # the program the cell runs since PR 41, under the crossover: the dense
    # product over its own [512, 512], no column of the 1,024-wide table
    ("prefill", 1, 512, dict(head="last", table_first=True, fresh=True), 0.5e9, 2048),
], ids=["decode-48", "prefill-512", "prefill-512-fresh"])
def test_xing4_engine_steps_compile_beside_weights_and_pool(v5e, name, B, S, kw, scratch_under,
                                                            alloc_under):
    """`serve-xing-midin-384-out`'s two largest programs as the engine builds
    them, at Xing4.0-29B-A4B's published widths and FULL depth (2 dense + 38
    expert layers, 8 of 64 experts, the whole vocabulary, four residual
    streams): 48 slots, a 64-block table, the latent pool `bf16[40, 2689, 16,
    640]` (2.20 GB) donated beside 12.15 GB of weights. They compile for a v5e
    (an out-of-HBM or Mosaic refusal fails here, not on the chip); the pool,
    carried through both scans beside FOUR streams, stays in place (PR 33's
    first finding: the only pool-shaped instructions are the `latent_write`
    scatters) and no layer's experts are copied (its second: scratch stays
    under one layer's held experts, 176 MB); the text names the scopes a
    profile is read by, the hyper-connections' three among them. The
    compiler's bytes go into the configuration file's `num_blocks_note`."""
    cfg, engine = _xing4_serve_ep8()
    assert (engine["max_batch_size"], engine["num_blocks"], engine["block_size"]) == (48, 2689, 16)
    assert engine["prefill_buckets"][-1] == 512 and cfg.cache_layers == 40 and cfg.hyper.n == 4
    lowered, pool = _engine_step(v5e[0], cfg, name, B=B, S=S, max_blocks=64,
                                 pool_blocks=engine["num_blocks"], **kw)
    compiled = lowered.compile()
    assert pool["latent"].shape == (40, 2689, 16, 640)
    assert set(pool["counters"]) == {"moe_rows", "moe_moved", "hc_residue"}
    assert_pool_stays_in_place(compiled, pool, scratch_under=int(scratch_under),
                               alloc_under=alloc_under)
    ma = compiled.memory_analysis()
    print(name, ma.argument_size_in_bytes, ma.output_size_in_bytes, ma.temp_size_in_bytes)
    # weights 12.15 GB + pool 2.20 GB, and the step's scratch: under 15.0 GB
    assert 14.3e9 < ma.argument_size_in_bytes < 14.4e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
    # no copy of a layer's held experts (3 x 8 x 3584 x 1024 bf16 = 176 MB)
    assert ma.temp_size_in_bytes < 3 * 8 * 3584 * 1024 * 2
    text = compiled.as_text()
    fresh = kw.get("fresh", False)
    names = ["lead/", "hc/map", "hc/sinkhorn", "hc/mix", "attn/latent_write",
             "attn/prompt_attend" if fresh else "attn/latent_read", "moe/route",
             "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "grouped_matmul_fwd"]
    for scope in names + (["attn/absorb", "latent_attention_decode"] if S == 1 else []):
        assert scope in text, scope
    assert ("latent_attention_decode" in text) == (S == 1)
    assert_a_fresh_latent_prefill_reads_its_own_rows(text, S, fresh)
    if fresh:
        assert_a_fresh_prefill_writes_whole_pages(text, pool["latent"].shape, S,
                                                  "attn/latent_write", leaves=1)
    if S == 512:   # scores against the table's 1,024 columns, or against its own 512 rows
        wide = re.findall(r"= f32\[[\d,]+,512,1024\]", text)   # by head: not the combine's
        assert bool(wide) == (not fresh), wide[:3]
    # the prefill's expert layers gather 512 of their 2,048 pairs a trip; the
    # decode step moves its 192 pairs, and its text is what it was
    assert_a_share_moves_its_bound(text, cfg, B * S, compacts=S == 512)


def _lfm2_serve_ep2():
    """The model of `lfm2-8b-a1b-serve-ep2-1chip`, from the cell's own file,
    and the file's engine section."""
    import json
    import os

    from benchmarks.harness.families import lfm2 as family

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "lfm2-8b-a1b-serve-ep2-1chip.json")
    with open(path) as f:
        file = json.load(f)
    return family.model_config({k: file[k] for k in family.MODEL_KEYS}), file["engine"]


@pytest.mark.parametrize("name, B, S, kw, scratch_under", [
    ("decode", 32, 1, dict(head=0), 0.05e9),
    ("prefill", 1, 2048, dict(head="last", table_first=True, fresh=True), 0.3e9),
    ("prefill", 1, 4096, dict(head="last", table_first=True, fresh=True), 0.4e9),
], ids=["decode-32", "prefill-2048", "prefill-4096"])
def test_lfm2_engine_steps_compile_beside_weights_and_pool(v5e, name, B, S, kw, scratch_under):
    """`serve-lfm2-docs4k-96-out`'s three programs as the engine builds them,
    at LFM2-8B-A1B's published widths and FULL depth (18 convolution and 6
    attention layers in 13 runs over three parameter stacks, 16 of 32 experts,
    the whole vocabulary, the head tied): 32 slots, a 264-block table, the
    pool's THREE leaves donated beside 8.93 GB of weights: `k`, `v`
    `bf16[6, 8385, 16, 1024]` (64-wide heads in 128-lane tiles) and `conv`
    `bf16[18, 8385, 2, 2048]`, 4.53 GB. They compile for a v5e; every leaf
    stays in place through the runs' scans and bare bodies (the only
    pool-shaped instructions are the row scatters of `attn/kv_write` and
    `conv/state_write`), a run that is part of its stack copies no slice of
    it and no layer's experts are copied (scratch stays under one layer's
    held experts, 352 MB, and far under a stack's 16 convolution mixers); the
    text names the scopes a profile is read by. The compiler's bytes go into
    the configuration file's `num_blocks_note`."""
    cfg, engine = _lfm2_serve_ep2()
    assert (engine["max_batch_size"], engine["num_blocks"], engine["block_size"]) == (32, 8385, 16)
    assert engine["prefill_buckets"] == [2048, 4096]
    assert (cfg.cache_layers("conv"), cfg.cache_layers("attn"), cfg.base.hd) == (18, 6, 64)
    lowered, pool = _engine_step(v5e[0], cfg, name, B=B, S=S, max_blocks=264,
                                 pool_blocks=engine["num_blocks"], **kw)
    compiled = lowered.compile()
    assert pool["k"].shape == pool["v"].shape == (6, 8385, 16, 1024)
    assert pool["conv"].shape == (18, 8385, 2, 2048)
    assert set(pool["counters"]) == {"moe_rows", "moe_moved"}
    assert_pool_stays_in_place(compiled, pool, scratch_under=int(scratch_under), alloc_under=2048)
    ma = compiled.memory_analysis()
    print(name, S, ma.argument_size_in_bytes, ma.output_size_in_bytes, ma.temp_size_in_bytes)
    # weights 8.93 GB + pool 4.53 GB, and the step's scratch: under 15.0 GB
    assert 13.4e9 < ma.argument_size_in_bytes < 13.5e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
    text = compiled.as_text()
    # the two rows of a block are not padded to a tile of 8 or 16 sublanes
    assert "bf16[18,8385,2,2048]{3,2,1,0:T(2,128)(2,1)}" in text
    names = ["conv/in_proj", "conv/mix", "conv/state_write", "attn/kv_write", "moe/route",
             "moe/dispatch", "moe/experts", "moe/combine", "grouped_matmul_fwd", "mlp/"]
    names += (["conv/state_read", "attn/kv_read", "paged_attention_decode"] if S == 1
              else ["attn/prompt_attend", "flash_attention_fwd"])
    for scope in names:
        assert scope in text, scope
    # a fresh prefill reads no state and no K/V back
    assert ("conv/state_read" in text) == ("attn/kv_read" in text) == (S == 1)
    # and writes its six attention layers' keys and values as whole pages (the
    # state's two rows a block stay a row scatter, `conv/state_write`)
    if S > 1:
        assert_a_fresh_prefill_writes_whole_pages(text, pool["k"].shape, S, "attn/kv_write",
                                                  leaves=2)
    # 16 of 32 held: the bound is every pair, the step moves T x k rows
    assert_a_share_moves_its_bound(text, cfg, B * S, compacts=False)


def _nemotron_serve_ep8():
    """The model of `nemotron-3-nano-30b-a3b-serve-ep8-1chip`, from the cell's
    own file, and the file's engine section."""
    import json
    import os

    from benchmarks.harness.families import nemotron_h as family

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "nemotron-3-nano-30b-a3b-serve-ep8-1chip.json")
    with open(path) as f:
        file = json.load(f)
    return family.model_config({k: file[k] for k in family.MODEL_KEYS}), file["engine"]


def _nemotron_engine_step(d, cfg, engine, name, S, **kw):
    """(lowered, the pool's shapes): a step of `serve-nemotron-tools4k-256-out`
    as the engine builds it (`paged_step`), the state page the last column
    of the 273-wide table: every slot at S == 1, one sequence at a prefill."""
    from ray_tpu.serve.llm_paged import paged_step

    slots, blocks = engine["max_batch_size"], engine["num_blocks"]
    place = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    model, i32, B = model_of(cfg), jnp.int32, slots if S == 1 else 1
    pool = jax.eval_shape(lambda: model.init_kv_pool(cfg, blocks, 16, num_sequences=slots + 1))
    params = place(jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0))))
    rest = ((_on(d, (1, 273), i32), _on(d, (2,), i32)) if S > 1
            else (_on(d, (B,), i32), _on(d, (B, 273), i32)))
    return paged_step(name, cfg, 16, "tpu", **kw).lower(
        params, place(pool), _on(d, (B, S), i32), *rest), pool


@pytest.mark.parametrize("name, S, kw, scratch_under", [
    ("decode", 1, dict(head=0), 0.35e9),
    ("prefill", 4096, dict(head="last", table_first=True, fresh=True), 0.8e9),
], ids=["decode", "prefill-4096"])
def test_nemotron_engine_steps_compile_beside_weights_and_pool(v5e, name, S, kw, scratch_under):
    """`serve-nemotron-tools4k-256-out`'s decode step and its 4,096 prefill as
    the engine builds them, at Nemotron-3-Nano-30B-A3B's published widths and
    FULL depth (52 blocks of one sub-layer: 23 Mamba-2, 6 attention, 23 expert
    blocks of 16 held un-gated experts; 52 unrolled runs), the engine's slots
    and blocks, a 272-block table with the state page as its last column, the
    pool's FOUR leaves of two classes donated beside 10.5 GB of weights. They
    compile for a v5e; every leaf stays in place (the only pool-shaped
    instructions are the scatters of `attn/kv_write` and `ssm/state_write`:
    no copy of the state leaf, no pool-sized temporary), no layer's experts
    are copied (the un-gated up-projection is held transposed: as `[E, H,
    1856]` the TPU lays it H-minor and the kernel is handed a 3.5 GB copy of
    the stack), and the text names the scopes a profile is read by. The
    compiler's bytes go into the configuration file's `num_blocks_note`."""
    cfg, engine = _nemotron_serve_ep8()
    slots, blocks = engine["max_batch_size"], engine["num_blocks"]
    assert engine["block_size"] == 16 and engine["prefill_buckets"] == [2048, 4096]
    assert blocks == slots * 272 + 1
    assert (cfg.count("mamba"), cfg.count("attn"), cfg.count("experts")) == (23, 6, 23)
    assert model_of(cfg).sequence_leaves == ("ssm", "conv")
    lowered, pool = _nemotron_engine_step(v5e[0], cfg, engine, name, S, **kw)
    params = lowered.args_info[0][0]
    compiled = lowered.compile()
    assert pool["k"].shape == pool["v"].shape == (6, blocks, 16, 256)
    assert pool["ssm"].shape == (23, slots + 1, 64, 64, 128) and pool["ssm"].dtype == jnp.float32
    assert pool["conv"].shape == (23, slots + 1, 3 * 6144)
    assert params["experts"]["e_up_t"].shape == (23, 16, 1856, 2688)
    # `conv` is 41 MB, and XLA:TPU moves a leaf that small into the core's 128
    # MiB of VMEM (`S(1)`) and back, once around a prefill, a few times in a
    # decode step: it is held to a count, the three large leaves to staying
    # where they are. The scan's zero carry
    # f32[1, 8, 8, 64, 128] is the one buffer allocated inside, 2 MB
    large = {k: v for k, v in pool.items() if k != "conv"}
    writes = ("scatter", "dynamic-update-slice")
    assert_pool_stays_in_place(compiled, large, scratch_under=int(scratch_under),
                               alloc_under=2 ** 22, writes=writes)
    moved = [op for op, ln in pool_sized_instructions(compiled.as_text(), pool["conv"].shape)
             if op not in ("parameter", "get-tuple-element", "bitcast", *writes)]
    assert len(moved) < 23, moved    # a few moves a step, not one a layer
    ma = compiled.memory_analysis()
    print(name, S, ma.argument_size_in_bytes, ma.output_size_in_bytes, ma.temp_size_in_bytes)
    weights = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert 10.5e9 < weights < 10.55e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
    text = compiled.as_text()
    # no copy of a layer's (or the stack's) experts, in any layout
    assert not re.search(r"= bf16\[(23,)?16,(1856,2688|2688,1856)\]\S* (copy|fusion|transpose)\(", text)
    names = ["ssm/in_proj", "ssm/conv", "ssm/state_write", "ssm/gate_norm", "attn/kv_write",
             "moe/route", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared",
             "grouped_matmul_fwd_nt", "grouped_matmul_fwd"]
    names += (["ssm/state_read", "ssm/step", "ssm_state_step", "attn/kv_read",
               "paged_attention_decode"] if S == 1
              else ["ssm/scan", "attn/prompt_attend", "flash_attention_fwd"])
    for scope in names:
        assert scope in text, scope
    # a fresh prefill reads no state and no K/V back; no rotation anywhere
    assert ("ssm/state_read" in text) == ("attn/kv_read" in text) == (S == 1)
    assert "cosine" not in text and "sine" not in text
    if S > 1:
        assert_a_fresh_prefill_writes_whole_pages(text, pool["k"].shape, S, "attn/kv_write",
                                                  leaves=2)
        # an expert block sums its held rows back an even share and a quarter
        # at a time (PR 47): the combine's one-hot is [4096, 3840], where four
        # even shares made it bf16[4096, 12288], 100.7 MB written and read and
        # 270.6 GFLOP a block: 6.22 of the program's 18.85 TFLOP for ~2,864
        # live rows
        trip = moe.held_rows_trip(S * 6, 16, 128)
        assert trip == 3840 and array_lines(text, (S, trip)) and not array_lines(text, (S, 12288))
        assert_a_share_moves_its_bound(text, cfg, S, compacts=True)
        flops = compiled.cost_analysis()["flops"]
        print("flops", flops)
        assert 12e12 < flops < 15e12, flops
    else:
        # the decode step's state update is the in-place kernel: no gathered
        # copy of the live pages, f32[slots, 64, 64, 128], exists
        assert not array_lines(text, (slots, 64, 64, 128))


def _laguna_serve_ep8():
    """The model of `laguna-s-2.1-serve-ep8-12l-1chip`, from the cell's own
    file, and the file's engine section."""
    import json
    import os

    from benchmarks.harness.families import laguna as family

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "laguna-s-2.1-serve-ep8-12l-1chip.json")
    with open(path) as f:
        file = json.load(f)
    return family.model_config({k: file[k] for k in family.MODEL_KEYS}), file["engine"]


def _laguna_engine_step(d, cfg, engine, name, S, **kw):
    """(lowered, the pool's shapes): a step of `serve-laguna-code8k-256-out` as
    the engine builds it (`paged_step`), the ring's id the last column of the
    529-wide table: every slot at S == 1, one sequence at a prefill."""
    from ray_tpu.serve.llm_paged import paged_step

    slots, blocks = engine["max_batch_size"], engine["num_blocks"]
    place = lambda tree: jax.tree.map(lambda a: _on(d, a.shape, a.dtype), tree)
    model, i32, B = model_of(cfg), jnp.int32, slots if S == 1 else 1
    pool = jax.eval_shape(lambda: model.init_kv_pool(cfg, blocks, 16, num_sequences=slots + 1))
    params = place(jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0))))
    rest = ((_on(d, (1, 529), i32), _on(d, (2,), i32)) if S > 1
            else (_on(d, (B,), i32), _on(d, (B, 529), i32)))
    return paged_step(name, cfg, 16, "tpu", **kw).lower(
        params, place(pool), _on(d, (B, S), i32), *rest), pool


@pytest.mark.parametrize("name, S, kw, scratch_under", [
    ("decode", 1, dict(head=0), 2 ** 25),
    ("prefill", 8192, dict(head="last", table_first=True, fresh=True), 1.5e9),
], ids=["decode", "prefill-8192"])
def test_laguna_engine_steps_compile_beside_weights_and_pool(v5e, name, S, kw, scratch_under):
    """`serve-laguna-code8k-256-out`'s decode step and its 8,192 prefill as the
    engine builds them, at Laguna-S-2.1's published widths and the first
    stage's 12 layers (layer 0 dense, 2 full and 9 sliding expert layers of 32
    held experts; 48 query heads in a full layer and 72 in a window one over 8
    key-value heads: groups of 6 and 9), the engine's slots and blocks, a
    528-block table with the ring's id as its last column, the pool's FOUR
    leaves of two classes donated beside 8.65 GB of weights. They compile for a
    v5e; every leaf stays in place (the only pool-shaped instructions are the
    writes of `kv_write`: a row scatter a step, whole pages and one ring a
    prefill; the ring read as pages of 128 rows is a bitcast), no layer's
    experts are copied, a decode step re-lays out no weight, and the text
    names the scopes and kernels a profile is read by. The compiler's bytes go
    into the configuration file's `num_blocks_note`."""
    cfg, engine = _laguna_serve_ep8()
    slots, blocks = engine["max_batch_size"], engine["num_blocks"]
    assert engine["block_size"] == 16 and engine["prefill_buckets"] == [2048, 4096, 8192]
    assert blocks == slots * 528 + 1
    assert (cfg.cache_layers("full"), cfg.cache_layers("win")) == (3, 9)
    model = model_of(cfg)
    assert model.sequence_leaves == ("k_win", "v_win")
    lowered, pool = _laguna_engine_step(v5e[0], cfg, engine, name, S, **kw)
    params = lowered.args_info[0][0]
    compiled = lowered.compile()
    assert pool["k"].shape == pool["v"].shape == (3, blocks, 16, 1024)
    assert pool["k_win"].shape == pool["v_win"].shape == (9, slots + 1, 512, 1024)
    assert params["win"]["e_gate"].shape == (9, 32, 3072, 1024)
    assert params["win"]["wq"].shape == (9, 3072, 72 * 128)
    assert params["full"]["wq"].shape == (2, 3072, 48 * 128)
    writes = ("scatter", "dynamic-update-slice")
    assert_pool_stays_in_place(compiled, pool, scratch_under=int(scratch_under),
                               alloc_under=2 ** 22, writes=writes)
    ma = compiled.memory_analysis()
    print(name, S, ma.argument_size_in_bytes, ma.output_size_in_bytes, ma.temp_size_in_bytes)
    weights = sum(math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert 8.65e9 < weights < 8.66e9
    assert 13.57e9 < ma.argument_size_in_bytes < 13.59e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.0e9
    text = compiled.as_text()
    # no copy of a layer's (or a stack's) experts, in any layout
    assert not re.search(
        r"= bf16\[(9,|2,)?32,(3072,1024|1024,3072)\]\S* (copy|fusion|transpose)\(", text)
    names = ["attn_full/attn/kv_write", "attn_win/attn/kv_write", "attn_full/attn/gate",
             "attn_win/attn/gate", "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
             "moe/shared", "grouped_matmul_fwd", "cosine"]
    names += (["attn_full/attn/kv_read", "attn_win/attn/kv_read", "paged_attention_decode",
               "paged_attention_window"] if S == 1
              else ["attn_full/attn/prompt_attend", "attn_win/attn/prompt_attend",
                    "flash_attention_fwd", "flash_attention_window"])
    for scope in names:
        assert scope in text, scope
    # a fresh prefill reads no K/V back; a decode step runs no flash kernel
    assert ("attn/kv_read" in text) == ("paged_attention_window" in text) == (S == 1)
    assert ("attn/prompt_attend" in text) == ("flash_attention_window" in text) == (S > 1)
    if S == 1:
        assert not weight_relayouts(text, params)
        # the ring is read where it lies: as 164 x 4 pages of 128 rows it is a bitcast
        ring_pages = array_lines(text, (9, (slots + 1) * 4, 128, 1024))
        assert ring_pages and all(" bitcast(" in ln for ln in ring_pages), ring_pages[:3]
    else:
        assert_a_fresh_prefill_writes_whole_pages(text, pool["k"].shape, S,
                                                  "attn_full/attn/kv_write", leaves=2)
        # a sparse layer sums its held rows back an even share and a quarter at
        # a time: 12,800 of the 81,920 pairs
        trip = moe.held_rows_trip(S * 10, 32, 256)
        assert trip == 12800 and array_lines(text, (S, trip))
        assert_a_share_moves_its_bound(text, cfg, S, compacts=True)


def _engine_step(d, cfg, name, *, B, S, max_blocks=128, pool_blocks, bs=16, **kw):
    """(lowered, the pool's shapes): the engines' own jitted step `name`
    (`serve/llm_paged.py::paged_step`, pool donated) told it runs on a TPU,
    lowered for device d. A prefill (`table_first`) takes a span as its fifth
    argument, any other step the lengths and the tables."""
    from ray_tpu.serve.llm_paged import paged_step

    _, params, pool, pool_shapes = _placed_model(d, cfg, pool_blocks, bs)
    i32 = jnp.int32
    rest = ((_on(d, (1, max_blocks), i32), _on(d, (2,), i32)) if kw.get("table_first")
            else (_on(d, (B,), i32), _on(d, (B, max_blocks), i32)))
    return (paged_step(name, cfg, bs, "tpu", **kw).lower(
        params, pool, _on(d, (B, S), i32), *rest), pool_shapes)


def _mistral_serve_16l():
    """The model of `mistral-7b-v0.3-serve-16l`, from the cell's own file."""
    import json
    import os

    from benchmarks.harness.families import llama as family

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "mistral-7b-v0.3-serve-16l.json")
    with open(path) as f:
        file = json.load(f)
    return family.model_config({k: file[k] for k in family.MODEL_KEYS})


@pytest.mark.parametrize("cfg, bucket, pool_blocks, scratch_under", [
    pytest.param(_mistral_serve_16l(), 2048, 4097, 2 ** 29, id="mistral-16l-2048"),
    pytest.param(dataclasses.replace(ouro.OuroConfig.ouro_2_6b(), max_seq_len=2048),
                 256, 321, 2 ** 30, id="ouro-2.6b-256"),
])
def test_prefill_runs_the_head_on_the_one_row_it_hands_back(
        v5e, cfg, bucket, pool_blocks, scratch_under):
    """The serving cells' largest prefill, whole, as the engine builds it:
    the head's product is `[1, H] x [H, V]`, the step returns `f32[1, V]`, and
    NO instruction of the compiled program produces an array with both the
    bucket and the vocabulary among its dimensions (the all-positions step
    had the `bf16[bucket, V]` product and its `f32[bucket, V]` twin, 268 MB at
    2,048 x 32,768, which the host then copied to read one row; PERF.md
    section 6, PR 32). The pool stays where it is, as before. Scratch: the
    attention's `bf16[8, 4, 2048, 2048]` probabilities (268 MB) lived in the
    logits' output buffer and now count as scratch, so output + scratch did
    not fall at Mistral's sizes (290.4 -> 290.7 MB)."""
    lowered, pool = _engine_step(v5e[0], cfg, "prefill", B=1, S=bucket,
                                 pool_blocks=pool_blocks, head="last", table_first=True)
    H, V = cfg.hidden_size, cfg.vocab_size
    assert re.search(rf"stablehlo\.dot_general .*tensor<1x1x{H}xbf16>, tensor<{H}x{V}xbf16>",
                     lowered.as_text())
    compiled = lowered.compile()
    assert_pool_stays_in_place(compiled, pool, scratch_under=scratch_under)
    text = compiled.as_text()
    both = [ln.strip()[:160] for ln in text.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]", ln))
            and {str(bucket), str(V)} <= set(m.group(1).split(","))]
    assert not both, both
    assert re.search(rf"ENTRY .*-> \(f32\[1,{V}\]", text)
    assert compiled.memory_analysis().output_size_in_bytes < (
        2 * math.prod(pool["k"].shape) * 2 + 2 ** 20)   # the pool and one row


@pytest.mark.parametrize("bucket, fresh", [(2048, True), (128, True), (2048, False)],
                         ids=["fresh-2048", "fresh-128", "continued-2048"])
def test_a_fresh_prefill_reads_its_own_rows_and_a_continued_one_the_table(v5e, bucket, fresh):
    """`serve-docs-batch`'s prefill as the engine builds its two programs
    (`prefill_step`), at Mistral's widths, B = 1, the cell's 4,097-block pool
    donated. The one a prompt with no cached prefix takes (`fresh`): from the
    1,024 bucket up the flash forward kernel under `attn/prompt_attend`,
    nothing under `attn/kv_read` (no gather of the table), no float32 array
    with the bucket twice among its dimensions (the scores stay in the
    kernel's VMEM; the table program forms `f32[8, 4, 2048, 2048]`, 537 MB,
    a layer), and the pool where it is; at the 128 bucket the dense product
    over [128, 128], no array as wide as the table. The one a prompt that
    continues a cached prefix takes still compiles, reads the table, and is
    what the detector finds the scores in."""
    lowered, pool = _engine_step(v5e[0], _mistral_serve_16l(), "prefill", B=1, S=bucket,
                                 pool_blocks=4097, head="last", table_first=True,
                                 fresh=fresh)
    compiled = lowered.compile()
    assert_pool_stays_in_place(compiled, pool, scratch_under=2 ** 29)
    text = compiled.as_text()
    f32_dims = [m.group(1).split(",") for m in re.finditer(r"= f32\[([\d,]+)\]", text)]
    scores = [d for d in f32_dims if d.count("2048") >= 2]
    assert "attn/kv_write" in text
    if fresh:
        assert_a_fresh_prefill_writes_whole_pages(text, pool["k"].shape, bucket, "attn/kv_write",
                                                  leaves=2)
    else:   # the continued prefix's start is traced: a row a token, as the parent's
        assert set(pool_scatter_updates(text, pool["k"].shape)) == {(bucket, 1024)}
    assert ("attn/prompt_attend" in text) == fresh
    assert ("attn/kv_read" in text) == (not fresh)
    assert ("flash_attention_fwd" in text) == (fresh and bucket >= 1024)
    assert bool(scores) == (not fresh), scores[:3]
    if bucket == 128:
        assert not [d for d in f32_dims if "2048" in d]
        assert ["1", "8", "4", "128", "128"] in f32_dims or ["8", "4", "128", "128"] in f32_dims


def test_ouro_s_fresh_prefill_writes_every_pass_s_pages(v5e):
    """`serve-ouro-shortin-batch`'s largest prefill as the engine builds it
    for a prompt with no cached prefix, at Ouro-2.6B's published widths: the
    256 bucket is 16 pages a cache layer, `pass * L + layer` traced inside two
    scans, 192 of them an admission (384 scatters of 256 rows, 14 ms of every
    ~96 ms admission, before PR 43). Whole pages, the pool `bf16[192, 321, 16,
    2048]` where it is, scratch under the bound the table program has."""
    cfg = dataclasses.replace(ouro.OuroConfig.ouro_2_6b(), max_seq_len=2048)
    lowered, pool = _engine_step(v5e[0], cfg, "prefill", B=1, S=256, pool_blocks=321,
                                 head="last", table_first=True, fresh=True)
    compiled = lowered.compile()
    assert_pool_stays_in_place(compiled, pool, scratch_under=2 ** 30)
    text = compiled.as_text()
    assert_a_fresh_prefill_writes_whole_pages(text, pool["k"].shape, 256, "attn/kv_write",
                                              leaves=2)
    assert "attn/prompt_attend" in text and "attn/kv_read" not in text


def test_steps_that_read_every_row_or_none_keep_or_drop_the_head(v5e):
    """What `paged_step(head=)` makes of the other steps, at a small size:
    `decode` (S = 1, the only row is every row) is the all-positions program,
    letter for letter the text of a step spelled without `head_rows`;
    `draft_decode2` takes row 1 BEFORE the head; `draft_prefill`, whose logits
    nobody reads, compiles to a program with no product over the vocabulary
    at all."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.bfloat16, hidden_size=512,
        intermediate_size=1024, num_heads=4, num_kv_heads=2, head_dim=128,
        num_layers=3, vocab_size=640)
    d, size = v5e[0], dict(max_blocks=8, pool_blocks=257)
    decode, _ = _engine_step(d, cfg, "decode", B=8, S=1, head=0, **size)

    def spelled(params, pool, tokens, lengths, tables):
        logits, pool = llama.forward_paged(params, tokens, cfg, pool, tables, lengths,
                                           16, platform="tpu")
        return logits[:, 0], pool

    spelled.__name__ = spelled.__qualname__ = "decode"
    args = jax.tree.map(lambda a: _on(d, a.shape, a.dtype), decode.args_info[0],
                        is_leaf=lambda a: hasattr(a, "shape"))
    # the kernel's serialised body carries the source lines of who called it
    bare = lambda lowered: re.sub(r'backend_config = "[^"]*"', "", lowered.as_text())
    assert "tpu_custom_call" in bare(decode)
    assert bare(decode) == bare(jax.jit(spelled, donate_argnums=(1,)).lower(*args))

    two, _ = _engine_step(d, cfg, "draft_decode2", B=8, S=2, head=1, **size)
    assert "tensor<8x1x512xbf16>, tensor<512x640xbf16>" in two.as_text()
    assert "tensor<8x2x512xbf16>, tensor<512x640xbf16>" not in two.as_text()

    none, _ = _engine_step(d, cfg, "draft_prefill", B=1, S=128, head=None,
                           table_first=True, **size)
    # the embedding is [640, 512]; logits, and the head's weights read, end in 640
    wide = lambda text: [ln.strip()[:160] for ln in text.splitlines() if (m := re.match(
        r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\S* ([\w-]+)\(", ln))
        and m.group(1).split(",")[-1] == "640" and m.group(2) != "parameter"]
    text = none.compile().as_text()
    assert wide(two.compile().as_text()) and not wide(text), wide(text)
    assert re.search(r"ENTRY .*-> \(bf16\[3,257,16,256\]", text)   # the pool alone comes back


def test_pool_sized_instruction_detector_sees_a_copy(v5e):
    """The detector is not vacuous: a pool that is not donated has to be
    copied before the first write, and that copy is found."""
    compiled, pool = _compiled_pool_step(v5e[0], B=8, S=1, donate=False)
    with pytest.raises(AssertionError):
        assert_pool_stays_in_place(compiled, pool)
    assert any(op.startswith("copy") for op, _ in pool_sized_instructions(
        compiled.as_text(), pool["k"].shape))


def _cell_decode_step(d, cell: str):
    """(the lowered decode step, its weights' shapes) of a serving cell as
    the engine builds the step (`head=0`), at the cell's slots, table and
    pool; `olmoe-1b-7b` is no cell's, the published OLMoE-1B-7B at 32 slots."""
    if cell == "nemotron":
        lowered = _nemotron_engine_step(d, *_nemotron_serve_ep8(), "decode", 1, head=0)[0]
        return lowered, lowered.args_info[0][0]
    if cell == "laguna":
        lowered = _laguna_engine_step(d, *_laguna_serve_ep8(), "decode", 1, head=0)[0]
        return lowered, lowered.args_info[0][0]
    if cell == "mistral-16l":
        cfg, size = _mistral_serve_16l(), dict(B=32, pool_blocks=4097)
    elif cell == "ouro-2.6b":
        cfg = dataclasses.replace(ouro.OuroConfig.ouro_2_6b(), max_seq_len=2048)
        size = dict(B=32, pool_blocks=321)
    elif cell == "olmoe-1b-7b":
        cfg, size = moe.MoEConfig.olmoe_1b_7b(), dict(B=32, pool_blocks=1025)
    else:
        cfg, engine = {"kimi": _kimi_serve_ep32, "xing4": _xing4_serve_ep8,
                       "lfm2": _lfm2_serve_ep2}[cell]()
        size = dict(B=engine["max_batch_size"], pool_blocks=engine["num_blocks"],
                    max_blocks={"kimi": 128, "xing4": 64, "lfm2": 264}[cell])
    lowered = _engine_step(d, cfg, "decode", S=1, head=0, **size)[0]
    return lowered, lowered.args_info[0][0]


@pytest.mark.parametrize("cell, slots, scratch_under", [
    ("mistral-16l", 32, 2 ** 20),
    # 387,584 B where the two hoisted re-layouts of `wq` and `wk` were 806,016,512
    ("ouro-2.6b", 32, 2 ** 20),
    ("kimi", 64, 2 ** 21),
    ("xing4", 48, 2 ** 24),
    ("lfm2", 32, 2 ** 24),
    ("nemotron", 48, 2 ** 25),
    ("laguna", 40, 2 ** 25),
    ("olmoe-1b-7b", 32, 2 ** 20),
])
def test_a_decode_step_reads_its_projections_weights_in_place(v5e, cell, slots, scratch_under):
    """Every serving cell's decode step as the engine builds it, at the
    published widths: NO instruction copies, transposes or stages in VMEM a
    layer's slice of a stacked weight or a whole stack, and scratch stays
    small. The projections that are split into heads hold their RESULT
    (`llama.project_heads`, S == 1), so the layout rope and the decode
    kernels want goes to `bf16[slots, N]` and the product takes the scan's
    slice of its stack as a fused operand, as the MLP's and `wo`'s do. Built
    the old way Mistral's step staged and transposed `wq` and `wk` a layer,
    Ouro's re-laid both whole stacks out a step (403 MB read and written
    each), Kimi's and Xing's did `w_uq`'s in both runs, LFM2's and Nemotron's
    copied `wq` (LFM2: and `wk`, `wv`) of their six attention layers; OLMoE's
    whole-vector norm stood between product and split and its step had none
    (PERF.md section 6, PR 46)."""
    lowered, params = _cell_decode_step(v5e[0], cell)
    assert lowered.args_info[0][2].shape == (slots, 1)
    compiled = lowered.compile()
    found = weight_relayouts(compiled.as_text(), params)
    assert not found, found
    assert compiled.memory_analysis().temp_size_in_bytes < scratch_under


@pytest.mark.parametrize("cell, copied", [
    # the scan's slices of `wq` and `wk` staged in VMEM, then transposed there: a layer
    ("mistral-16l", ["bf16[1,4096,1024]", "bf16[1,4096,4096]"]),
    # both stacks whole, hoisted out of the passes' scan: a step
    ("ouro-2.6b", ["bf16[48,2048,2048]", "bf16[48,2048,2048]"]),
])
def test_weight_relayout_detector_sees_the_old_projection(v5e, monkeypatch, cell, copied):
    """The detector is not vacuous: the decode step built the way it was
    until PR 46 (product, then split, nothing held) has the copies of `wq`
    and `wk` the ledger's breakdowns named, and they are found."""
    from tests.test_project_heads import unheld_project_heads

    monkeypatch.setattr(llama, "project_heads", unheld_project_heads)
    lowered, params = _cell_decode_step(v5e[0], cell)
    compiled = lowered.compile()
    found = weight_relayouts(compiled.as_text(), params)
    assert {op for op, _ in found} == {"copy", "fusion", "dynamic-slice"}, found
    assert sorted(re.search(r"= (\w+\[[\d,]+\])", ln).group(1)
                  for op, ln in found if op == "copy") == copied, found
    if cell == "ouro-2.6b":
        assert compiled.memory_analysis().temp_size_in_bytes > 8e8


def _train_attention_text(d) -> str:
    """The compiled HLO of the loss's gradient at a length that takes the
    flash kernel, under the remat the train step uses."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.bfloat16,
                              head_dim=64, max_seq_len=1024, remat=True)
    attn = functools.partial(llama.auto_attention, causal=True, platform="tpu")
    params = jax.tree.map(
        lambda a: _on(d, a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0))))
    tokens = _on(d, (1, 1024), jnp.int32)
    return jax.jit(jax.grad(
        lambda p, t: llama.loss_fn(p, t, t, cfg, attn_fn=attn))).lower(
            params, tokens).compile().as_text()


def test_paged_decode_step_takes_kernel_from_platform(v5e):
    """forward_paged told it runs on a TPU reaches the kernel with no stub of
    jax.devices — the decision is the caller's placement, not this host's."""
    assert MOSAIC in _decode_step_text(v5e[0])


@pytest.mark.parametrize("text_of, names", [
    (_decode_step_text, ("paged_attention_decode", "attn/kv_write",
                         "attn/kv_read/paged_attention_decode", "/mlp/")),
    (_train_attention_text, ("flash_attention_fwd", "flash_attention_dq",
                             "flash_attention_dkv", "/attn/flash_attention_fwd",
                             "/mlp/")),
], ids=["paged_decode_step", "train_attention"])
def test_compiled_text_names_kernels_and_scopes(v5e, text_of, names):
    """A profile of the chip shows operations under the names the compiled
    text carries: each Pallas kernel by its own `name=`, and the model's
    named scopes (attention, its paged KV write and read, the MLP) in
    `op_name`. The benchmark's reduction tells kernels apart by them."""
    text = text_of(v5e[0])
    for name in names:
        assert name in text, name
    kernels = _kernel_lines(text)
    assert kernels and all(
        ln.startswith(("%paged_attention_decode", "%flash_attention_",
                       "ROOT %paged_attention_decode", "ROOT %flash_attention_"))
        for ln in kernels)


def test_sharded_train_step_compiles_with_flash(v5e):
    """On today's main path: make_train_step over fsdp=2 x tensor=2 at a
    length that takes the flash kernel. Before default_attn_fn wrapped the
    kernel in shard_map this failed to lower."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.bfloat16,
                              head_dim=64, max_seq_len=1024, remat=True)
    mesh = make_mesh(4, fsdp=2, tensor=2, devices=v5e)
    opt = spmd.make_optimizer(warmup=1)
    state = jax.eval_shape(
        lambda: spmd.init_state(cfg, jax.random.PRNGKey(0), optimizer=opt))
    step = spmd.make_train_step(cfg, mesh, optimizer=opt)(state)
    sh = spmd.state_shardings(cfg, mesh, state)
    state_in = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), state, sh)
    batch = jax.ShapeDtypeStruct((4, 1024), jnp.int32,
                                 sharding=shd.batch_sharding(mesh))
    text = step.lower(state_in, batch, batch).compile().as_text()
    assert MOSAIC in text
    assert "all-reduce" in text or "reduce-scatter" in text


def test_make_mesh_refuses_missing_devices():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="device"):
        make_mesh(n + 1)


def test_grouped_matmul_compiles_at_the_olmoe_shapes(v5e):
    """The three grouped kernels at the shapes of the OLMoE cell's expert
    layer (2 sequences of 4,096: 65,536 sorted rows, 64 experts, 2048 x 1024
    and back), tiles from `choose_tiles`, by their names."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul

    sizes = _on(v5e[0], (64,), jnp.int32)
    for k, n in ((2048, 1024), (1024, 2048)):
        def loss(lhs, rhs, sizes):
            return grouped_matmul(lhs, rhs, sizes, platform="tpu").astype(jnp.float32).sum()

        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            _on(v5e[0], (65536, k)), _on(v5e[0], (64, k, n)), sizes).compile().as_text()
        for name in ("grouped_matmul_fwd", "grouped_matmul_dlhs", "grouped_matmul_drhs"):
            assert name in text, name


def test_moe_train_step_compiles_with_its_kernels_and_scopes(v5e):
    """`make_train_step` with the MoE model record on a one-chip TPU mesh, at
    a length that takes the flash kernel: the expert layer reaches the
    grouped kernels from the mesh's platform (this host's backend is the
    CPU), and the compiled text carries the names a profile is read by."""
    base = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.bfloat16,
                               hidden_size=256, intermediate_size=128, head_dim=128, num_heads=2,
                               num_kv_heads=2,
                               max_seq_len=1024, remat=True, remat_policy="dots")
    cfg = moe.MoEConfig(base=base, num_experts=8, top_k=2, qk_norm=True)
    mesh = make_mesh(1, devices=v5e[:1])
    opt = spmd.make_optimizer(warmup=1)
    state = jax.eval_shape(lambda: spmd.init_state(
        cfg, jax.random.PRNGKey(0), optimizer=opt, model=moe.MODEL))
    step = spmd.make_train_step(cfg, mesh, optimizer=opt, model=moe.MODEL)(state)
    sh = spmd.state_shardings(cfg, mesh, state, moe.MODEL)
    state_in = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), state, sh)
    batch = jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=shd.batch_sharding(mesh))
    text = step.lower(state_in, batch, batch).compile().as_text()
    for name in ("grouped_matmul_fwd", "grouped_matmul_dlhs", "grouped_matmul_drhs",
                 "flash_attention_fwd", "moe/route", "moe/dispatch",
                 "moe/experts/grouped_matmul_fwd", "moe/combine", "/attn/"):
        assert name in text, name
