"""No chip needed: compile every Pallas kernel for a TPU v5e.

Interpret mode does not enforce Mosaic's tiling rules (a block's last two
dims must be (8, 128)-divisible or equal the array's), so a kernel can pass
the whole CPU suite and still be refused by the TPU compiler — both decode
kernels were, for ten PRs.  ``libtpu`` can compile for a chip that is not
there: ``topologies.get_topology_desc`` describes a v5e 2x2 host, nothing
executes and no device is taken.  The kernel calls compiled here are the
ones ``chip_smoke.py`` runs on the chip, at the same BERT-large / GPT-350M
shapes; whole models (15-90 s each) are marked ``slow``.
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from apex_tpu.models.gpt import GPTConfig, GPTModel

_MOSAIC = "tpu_custom_call"


@pytest.fixture(scope="module")
def v5e_devices():
    return topologies.get_topology_desc("v5e:2x2", platform="tpu").devices


@pytest.fixture(autouse=True)
def _dispatch_as_on_tpu(monkeypatch):
    """Ops choose Pallas and switch the interpreter off exactly as they
    do on the chip (both read ``is_tpu_backend``)."""
    monkeypatch.setattr("apex_tpu.utils.platform.is_tpu_backend",
                        lambda: True)
    # keep TPU executables out of the suite's persistent cache: a
    # CPU-only process cannot load them back (it warns and recompiles)
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


def _abstract(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, args, sharding, **jit_kw):
    """Compile ``fn`` for the topology; Mosaic must be in the result."""
    compiled = jax.jit(fn, **jit_kw).lower(
        *_abstract(args, sharding)).compile()
    text = compiled.as_text()
    assert _MOSAIC in text, "compiled without a Mosaic custom call"
    return compiled


@pytest.mark.parametrize("name", list(chip_smoke.KERNEL_CHECKS))
def test_kernel_compiles_for_v5e(name, v5e_devices, monkeypatch):
    sharding = SingleDeviceSharding(v5e_devices[0])
    compiled = []
    monkeypatch.setattr(
        chip_smoke, "_kernel_vs_reference",
        lambda fn, args, tol, reorders=True, zero_rows=None:
        compiled.append(_compile(fn, args, sharding)) or 0.0)
    # only shapes matter to a compile: skip drawing 50M random numbers
    monkeypatch.setattr(
        chip_smoke, "_randn",
        lambda seed, shape, dtype, scale=1.0: jnp.zeros(shape, dtype))
    chip_smoke.KERNEL_CHECKS[name]()
    assert compiled


def _custom_calls(compiled):
    return [line.strip() for line in compiled.as_text().splitlines()
            if _MOSAIC in line and " custom-call(" in line]


@pytest.mark.parametrize("b,s,causal", [
    (16, 512, False),         # the BERT step's call: one dense block
    (1, 512, True),           # serving's longest prompt
    (1, 16, True),            # and its shortest, padded to a block of 128
])
def test_flash_rows_kernels_compile_for_v5e(b, s, causal, v5e_devices):
    """16 heads of 64, forward and backward, through the entry the models
    call: the three kernels take ``(b, s, 1024)`` rows, two heads to a
    128-lane tile, and nothing is padded to a head of 128."""
    from apex_tpu.ops.flash_attention import flash_attention_bshd
    x = jax.ShapeDtypeStruct((b, s, 16, 64), jnp.bfloat16)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda *a: flash_attention_bshd(
            *a, causal=causal), q, k, v)
        return out, vjp(out)

    calls = _custom_calls(_compile(fwd_bwd, (x, x, x),
                                   SingleDeviceSharding(v5e_devices[0])))
    assert len(calls) == 3, calls
    rows = rf"bf16\[{b},{max(s, 128)},1024\]"
    for call in calls:
        assert len(re.findall(rows, call)) >= 4, call     # q, k, v, result
        assert not re.search(r"bf16\[\d+,\d+,128\]", call), call


def test_hybrid_flash_kernels_keep_their_operands(v5e_devices):
    """32 query heads of 128 over 8 192 positions, K and V broadcast from
    2 heads as ``ParallelAttention`` does: outside the rule that packs
    heads into a tile, so the backward kernels still return
    ``bf16[32,8192,128]``, which is what ``flash_bwd_h128_roofline.train``
    finds them by."""
    from apex_tpu.ops.flash_attention import flash_attention_bshd
    q = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16)

    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention_bshd(
            q, jnp.repeat(k, 16, axis=2), jnp.repeat(v, 16, axis=2),
            causal=True), q, k, v)
        return out, vjp(out)

    calls = _custom_calls(_compile(fwd_bwd, (q, kv, kv),
                                   SingleDeviceSharding(v5e_devices[0])))
    metric = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                          "metrics", "flash_bwd_h128_roofline.train.json")
    with open(metric) as f:
        pattern = json.load(f)["args"]["pattern"]
    # in the model's step the backward kernels carry the remat scope's
    # name; here only the shapes the pattern asks for can be held
    assert pattern.startswith(r"^%attention[\w.]*")
    pattern = pattern.replace(r"^%attention[\w.]*", r"^%[\w.]*", 1)
    assert len(calls) == 3, calls
    assert sum(bool(re.search(pattern, call)) for call in calls) == 2, calls


def _bert_train_step(n_dev, v5e_devices, monkeypatch, layers=None):
    """The example's own step, lowered for ``n_dev`` chips of the 2x2
    host: one chip, or dp4 under ``shard_map`` (GSPMD around Mosaic calls
    is refused — "Mosaic kernels cannot be automatically partitioned").
    ``layers`` cuts BERT-large's depth and nothing else."""
    devices = list(v5e_devices[:n_dev])
    recipe = chip_smoke._bert_recipe()
    args = recipe.parse_args([
        "--config", "large", "--batch-size", str(16 * n_dev),
        "--seq-len", "512"])
    with monkeypatch.context() as m:
        # the recipe places real arrays; an absent chip holds none
        m.setattr(jax, "device_put", lambda x, *a, **k: x)
        if layers is not None:
            hidden, _, heads = recipe._CONFIGS["large"]
            m.setitem(recipe._CONFIGS, "large", (hidden, layers, heads))
        train_step, state, make_batch, _ = recipe.build(args,
                                                        devices=devices)
        batch = make_batch()
    mesh = jax.make_mesh((n_dev,), ("data",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))
    return train_step.lower(
        *_abstract(state, NamedSharding(mesh, P())),
        *_abstract(batch, NamedSharding(mesh, P("data"))))


def test_bert_step_moves_no_head_of_64(v5e_devices, monkeypatch):
    """A BERT-large step two layers deep, compiled for one v5e: attention
    reads q, k and v as ``(16, 512, 1024)`` rows where the QKV matmul left
    them, so the step holds no operand padded to a head of 128, no
    heads-major copy, and no ``(b, s, heads, 64)`` array at all (XLA gives
    one a layout with the sequence in the lanes and pays a transposing
    copy on each side of it)."""
    text = _bert_train_step(1, v5e_devices, monkeypatch,
                            layers=2).compile().as_text()
    assert _MOSAIC in text
    for shape in ("bf16[256,512,128]", "bf16[16,16,512,64]",
                  "bf16[16,512,16,64]", "bf16[16,512,16,192]"):
        assert shape not in text, shape
    # the fused projection's (b, s, 3h) result is never relaid either
    assert not re.search(r"bf16\[16,512,3072\]\{(?!2,1,0)", text)
    # forward, dq and dk/dv of each layer: the kernels that write or read
    # the (b*h, s, 1) logsumexp
    kernels = [line for line in text.splitlines()
               if _MOSAIC in line and " custom-call(" in line
               and "f32[256,512,1]" in line]
    assert len(kernels) == 2 * 3, kernels
    for line in kernels:
        assert "bf16[16,512,1024]" in line, line


def _gpt(num_layers):
    cfg = GPTConfig(dtype=jnp.bfloat16,
                    **dict(chip_smoke.GPT, num_layers=num_layers))
    model = GPTModel(cfg)
    return cfg, model, jax.eval_shape(model.init_params,
                                      jax.random.PRNGKey(0))


def _decode_programs(num_layers):
    """(name, fn, args) of the serving engines' device programs at
    GPT-350M width: 8 slots, 1024 positions, bf16 cache, block size 8."""
    cfg, model, params = _gpt(num_layers)
    h, d, bs = cfg.num_attention_heads, cfg.head_dim, 8
    ints = jax.ShapeDtypeStruct((8,), jnp.int32)
    cache = jax.ShapeDtypeStruct(
        (8, num_layers, 2, cfg.max_seq_len, h, d), jnp.bfloat16)
    nb = cfg.max_seq_len // bs
    pool = jax.ShapeDtypeStruct(
        (1 + 8 * nb, num_layers, 2, bs, h * d), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((8, nb), jnp.int32)
    return [
        ("prefill", model.prefill,
         (params, jax.ShapeDtypeStruct((1, 16), jnp.int32))),
        ("decode_step", model.decode_step, (params, ints, cache, ints)),
        ("decode_step_paged", model.decode_step_paged,
         (params, ints, pool, tables, ints)),
    ]


@pytest.mark.parametrize("index", range(3))
def test_decode_programs_compile_two_layers(index, v5e_devices):
    name, fn, args = _decode_programs(2)[index]
    _compile(fn, args, SingleDeviceSharding(v5e_devices[0]))


def test_decode_tick_reads_the_pool_in_place(v5e_devices):
    """The serving cell's decode tick (32 slots, 4 097 blocks of 8, bf16,
    the pool donated as ``PagedInferenceEngine`` donates it), two layers
    deep: the pool is neither relaid nor sliced.  Its rows are lane-dense,
    so XLA's default layout is the row-major one the Mosaic call demands,
    and the kernel addresses layer and K/V through its BlockSpec.  A pool
    whose minor dimension was ``head_dim`` 64 was copied whole into a
    padded row-major temporary and back, and sliced twice a layer, in
    every tick.  The tick is the engine's own: ``decode_step_paged`` with
    the rows' arg-max beside its logits (``_picking``), under the module
    name the benchmark's readers match."""
    from apex_tpu.inference.engine import _picking
    cfg, model, params = _gpt(2)
    slots, bs, blocks = 32, 8, 4097
    pool = jax.ShapeDtypeStruct(
        (blocks, 2, 2, bs, cfg.num_attention_heads * cfg.head_dim),
        jnp.bfloat16)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    tables = jax.ShapeDtypeStruct((slots, cfg.max_seq_len // bs), jnp.int32)
    compiled = _compile(_picking(model.decode_step_paged),
                        (params, ints, pool, tables, ints),
                        SingleDeviceSharding(v5e_devices[0]),
                        donate_argnums=(2,))
    pool_bytes = int(np.prod(pool.shape)) * 2
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.05 * pool_bytes
    assert memory.alias_size_in_bytes >= pool_bytes
    text = compiled.as_text()
    assert text.startswith("HloModule jit_decode_step_paged")
    logits, ids, out = compiled.out_info
    assert (ids.shape, ids.dtype) == ((slots,), jnp.int32)
    assert logits.shape == (slots, cfg.vocab_size) and out.shape == pool.shape
    # a layer's K or V is a quarter of this pool: nothing that large is
    # copied or sliced (the four in-place scatters are what remains)
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \(?\w+\[([\d,]+)\]\S* "
                     r"([\w-]+)\(", line)
        if m is None:
            continue
        name, dims, opcode = m.groups()
        if (np.prod([int(n) for n in dims.split(",")])
                >= np.prod(pool.shape) // 4
                and re.search("copy|slice",
                              name if opcode == "fusion" else opcode)):
            moved.append(line.strip()[:160])
    assert not moved, moved
    metric = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                          "metrics", "paged_decode_roofline.tpot.json")
    with open(metric) as f:
        pattern = json.load(f)["args"]["pattern"]
    kernels = [line for line in text.splitlines()
               if re.search(pattern, line.strip())]
    assert len(kernels) == 2, kernels      # one per layer


def _pool_program(program, args, device):
    """``program`` (one of ``serving/paged_kv.py``'s jitted, pool-donating
    writes) compiled for ``device`` on abstract ``args``."""
    return program.lower(*_abstract(
        args, SingleDeviceSharding(device))).compile()


def _assert_in_place(compiled, pool):
    pool_bytes = int(np.prod(pool.shape)) * pool.dtype.itemsize
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 0.05 * pool_bytes


def test_context_write_updates_the_pool_in_place(v5e_devices):
    """An admission's KV write at the serving cell's shapes (4 097 blocks
    of 8, 24 layers, a prompt bucket of 512, the prefill's KV as it came):
    the donated pool is the result, and what the program holds beside it
    is the bucket's rows, not a second pool.  The eager ``.at[].set()``
    it replaces copied 3.2 GB twice an admission."""
    from apex_tpu.serving.paged_kv import scatter_context_kv
    pool = jax.ShapeDtypeStruct((4097, 24, 2, 8, 1024), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((24, 2, 1, 512, 16, 64), jnp.bfloat16)
    compiled = _pool_program(
        scatter_context_kv,
        (pool, kv, jax.ShapeDtypeStruct((64,), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)), v5e_devices[0])
    _assert_in_place(compiled, pool)
    # the name by which kv_write_time_share.ttft finds the module
    metric = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                          "metrics", "kv_write_time_share.ttft.json")
    with open(metric) as f:
        pattern = json.load(f)["args"]["pattern"]
    module = re.search(r"HloModule (\S+?),", compiled.as_text()).group(1)
    assert re.search(pattern, module), module


@pytest.mark.parametrize("program", ["copy_block", "scatter_blocks",
                                     "fill_block"])
def test_block_writes_update_the_pool_in_place(program, v5e_devices):
    """Copy-on-write, a handoff's import of 16 blocks and the int8 pool's
    zero-on-alloc, two layers deep at the cell's 4 097 blocks: each takes
    the pool donated and returns it."""
    from apex_tpu.serving import paged_kv
    pool = jax.ShapeDtypeStruct((4097, 2, 2, 8, 1024), jnp.bfloat16)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    args = {"copy_block": (pool, scalar, scalar),
            "scatter_blocks": (
                pool, jax.ShapeDtypeStruct((16,), jnp.int32),
                jax.ShapeDtypeStruct((16,) + pool.shape[1:], pool.dtype)),
            "fill_block": (pool, scalar,
                           jax.ShapeDtypeStruct((), pool.dtype))}[program]
    _assert_in_place(
        _pool_program(getattr(paged_kv, program), args, v5e_devices[0]),
        pool)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("width", [128, 256])
def test_decode_tick_has_no_step_per_table_entry(width):
    """The tick's kernels iterate over slots, and inside a slot over the
    blocks its length says it holds (a loop bound the kernel reads): no
    static grid axis is the table's width, so a wider table, all else
    equal, adds no step."""
    cfg, model, params = _gpt(2)
    slots, bs = 32, 8
    pool = jax.ShapeDtypeStruct(
        (4097, 2, 2, bs, cfg.num_attention_heads * cfg.head_dim),
        jnp.bfloat16)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    tables = jax.ShapeDtypeStruct((slots, width), jnp.int32)
    calls = [eqn for eqn in _pallas_calls(
        jax.make_jaxpr(model.decode_step_paged)(
            params, ints, pool, tables, ints).jaxpr)
        if "decode_paged" in eqn.params["jaxpr"].debug_info.func_name]
    assert len(calls) == 2                 # one per layer
    for eqn in calls:
        grid = tuple(eqn.params["grid_mapping"].grid)
        assert grid == (slots,), grid
        assert width not in grid and width * bs not in grid


@pytest.mark.parametrize("bs,h,d", [(16, 16, 64), (8, 8, 128), (16, 2, 128),
                                    (8, 32, 32)])
def test_paged_decode_compiles_at_other_blocks_and_heads(bs, h, d,
                                                         v5e_devices):
    """Blocks of 16 and heads of 128 (the hybrid's two KV heads make a
    row of 256 lanes) and of 32: the shapes the DMA and the scratch take
    there are Mosaic's to refuse."""
    from apex_tpu.ops.flash_attention import flash_attention_decode_paged
    slots, nb = 8, 1024 // bs
    args = (jax.ShapeDtypeStruct((slots, h, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((1 + slots * nb, 3, 2, bs, h * d),
                                 jnp.bfloat16),
            jax.ShapeDtypeStruct((slots, nb), jnp.int32),
            jax.ShapeDtypeStruct((slots,), jnp.int32))
    _compile(lambda q, pool, tables, lens: flash_attention_decode_paged(
        q, pool, 1, tables, lens), args,
        SingleDeviceSharding(v5e_devices[0]))


@pytest.mark.parametrize("h,d", [(32, 80), (32, 96), (8, 256), (1, 64)])
def test_paged_decode_refuses_widths_it_cannot_read(h, d):
    """On a TPU a width outside the lane-dense kernel's reach raises: it
    never runs the gather and the jnp reference quietly."""
    from apex_tpu.ops.flash_attention import flash_attention_decode_paged
    q = jax.ShapeDtypeStruct((4, h, d), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((9, 2, 2, 8, h * d), jnp.bfloat16)
    tables = jax.ShapeDtypeStruct((4, 2), jnp.int32)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)
    with pytest.raises(ValueError, match="cannot read"):
        jax.eval_shape(
            lambda q, pool, tables, lens: flash_attention_decode_paged(
                q, pool, 1, tables, lens), q, pool, tables, lens)


@pytest.mark.slow
@pytest.mark.parametrize("index", range(3))
def test_decode_programs_compile_full_depth(index, v5e_devices):
    name, fn, args = _decode_programs(chip_smoke.GPT["num_layers"])[index]
    _compile(fn, args, SingleDeviceSharding(v5e_devices[0]))


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [1, 4])
def test_bert_large_train_step_compiles(n_dev, v5e_devices, monkeypatch):
    lowered = _bert_train_step(n_dev, v5e_devices, monkeypatch)
    assert _MOSAIC in lowered.compile().as_text()


@pytest.mark.slow
def test_gpt_dp2_tp2_sp_train_step_compiles(v5e_devices):
    """GPT-350M through ParallelPlan -> ElasticPlan.build ->
    pack_for_shard_map -> pipeline_step on the 2x2 host."""
    from apex_tpu.parallel.plan import ParallelPlan
    from apex_tpu.resilience.elastic import ElasticPlan
    from tools.autotune import build_train_step

    devices = list(v5e_devices[:4])
    plan = ParallelPlan(dp=2, tp=2, sequence_parallel=True)
    cfg_kw = dict(dtype=jnp.bfloat16, **chip_smoke.GPT)
    train_step, args, _ = build_train_step(
        plan, cfg_kw, 8, chip_smoke.GPT["max_seq_len"], devices)
    mesh = ElasticPlan.build(plan, devices=devices).mesh
    _compile(train_step, args, NamedSharding(mesh, P()),
             donate_argnums=(0, 1))


def test_lfm2_two_block_step_keeps_heads_of_64_in_their_rows(v5e_devices):
    """The ``lfm2`` recipe's own step at the published widths and the
    cell's shapes (2 x 8 192 tokens), two published layers deep (attention +
    experts, conv + experts, two experts held), compiled for one v5e: 32
    query heads of 64 go to ``flash_rows_fwd`` / ``flash_rows_bwd`` as
    ``(2, 8192, 2048)`` rows.  QK-norm, the rotary code and the broadcast of
    8 KV heads all work on rows, so the step holds no array split into
    heads of 64 (XLA would lay one out with the sequence in the lanes and
    copy it on each side) and none padded to 128 lanes; the experts' gate
    and up-projection are one grouped product of width 2 x 1 536."""
    recipe = chip_smoke._load("pretrain_lfm2", "examples", "lfm2",
                              "pretrain_lfm2.py")
    share = dict(recipe._CONFIGS["share"], layer_pattern="*ECE",
                 moe_held=(0, 2))
    built = {}

    def state():
        args = recipe.parse_args(["--config", "share", "--batch-size", "2",
                                  "--seq-len", "8192", "--lr", "1e-6"])
        built["step"], state, _, _ = recipe.build(
            args, devices=jax.devices()[:1])
        return state

    with pytest.MonkeyPatch.context() as m:
        m.setitem(recipe._CONFIGS, "share", share)
        shapes = jax.eval_shape(state)            # no weight is made
        chip = SingleDeviceSharding(v5e_devices[0])
        tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32, sharding=chip)
        text = built["step"].lower(
            *_abstract(shapes, chip), tokens, tokens).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if _MOSAIC in line and " custom-call(" in line]
    rows = "bf16[2,8192,2048]"
    fwd = [c for c in calls if c.startswith("%flash_rows_fwd")]
    bwd = [c for c in calls if c.startswith("%flash_rows_bwd")]
    assert len(fwd) == 2 and len(bwd) == 2, calls   # forward, recompute; dq, dk/dv
    for call in fwd + bwd:
        assert call.count(rows) >= 4, call          # q, k, v and a result
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                           "metrics",
                           "flash_rows_bwd_causal_roofline.train.json")) as f:
        pattern = re.compile(json.load(f)["args"]["pattern"])
    assert [c for c in calls if pattern.search(c)] == bwd
    # nothing is split into heads of 64, padded to 128 lanes or made
    # heads-major anywhere in the step
    assert not re.search(r"\[2,8192,\d+,(\d+,)?(32|64)\]", text)
    for shape in ("bf16[64,8192,128]", "bf16[64,8192,64]",
                  "bf16[2,32,8192,64]", "bf16[8192,2,32,64]"):
        assert shape not in text, shape
    grouped = [c.split(" custom-call(")[0] for c in calls
               if c.startswith("%ragged-dot-none")]
    # two experts held: chunks of 4 096 sorted pairs; forward and recompute
    assert sum("f32[4096,3072]" in c for c in grouped) >= 2 * 2, grouped


def test_smallthinker_step_trains_through_the_grouped_kernels(v5e_devices):
    """The ``smallthinker`` recipe's own step at the published widths, a
    full layer and a window layer (each with two of its experts), compiled
    for one v5e at 4 096 positions: each layer's attention is one forward,
    its recompute and one dQ and one dK/dV call, named by kind, of 28 query
    heads over 4 KV heads of 128 as rows, and no K or V is broadcast to the
    query heads; the two new metrics' patterns read exactly their calls."""
    recipe = chip_smoke._load("pretrain_smallthinker", "examples",
                              "smallthinker", "pretrain_smallthinker.py")
    share = dict(recipe._CONFIGS["share"], layer_pattern="*E*E",
                 attention_types=("full", "sliding"), rotary_layers=(0, 1),
                 moe_held=(0, 2))
    built = {}

    def state():
        args = recipe.parse_args(["--config", "share", "--seq-len", "4096",
                                  "--lr", "1e-6"])
        built["step"], state, _, _ = recipe.build(
            args, devices=jax.devices()[:1])
        return state

    with pytest.MonkeyPatch.context() as m:
        m.setitem(recipe._CONFIGS, "share", share)
        shapes = jax.eval_shape(state)            # no weight is made
        chip = SingleDeviceSharding(v5e_devices[0])
        tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=chip)
        text = built["step"].lower(
            *_abstract(shapes, chip), tokens, tokens).compile().as_text()
    calls = [line.strip() for line in text.splitlines()
             if _MOSAIC in line and " custom-call(" in line]
    named = {}
    for c in calls:
        kind = re.match(r"%(\w+?_flash_\w+?)\.\d+ = ", c)
        if kind:
            named.setdefault(kind.group(1), []).append(c)
    assert {k: len(v) for k, v in named.items()} == {
        "band_flash_fwd": 2, "band_flash_dq": 1, "band_flash_dkv": 1,
        "grouped_flash_fwd": 2, "grouped_flash_dq": 1,
        "grouped_flash_dkv": 1}, sorted(named)
    for name, found in named.items():
        for c in found:
            rows = "bf16[1,4096,512]" if name.endswith("dkv") \
                else "bf16[1,4096,3584]"
            assert c.split(" custom-call(")[0].count(rows) >= 1, c
    for metric, kinds in (
            ("band_flash_time_share.train",
             {"band_flash_fwd", "band_flash_dq", "band_flash_dkv"}),
            ("grouped_flash_bwd_roofline.train",
             {"grouped_flash_dq", "grouped_flash_dkv"})):
        with open(os.path.join(os.path.dirname(__file__), os.pardir,
                               "benchmarks", "metrics",
                               metric + ".json")) as f:
            pattern = re.compile(json.load(f)["args"]["pattern"])
        assert sorted(c for c in calls if pattern.search(c)) \
            == sorted(c for k in kinds for c in named[k])
    # no KV head repeated to its query heads, nothing split into heads
    assert not re.search(r"\[1,4096,(28|4),128\]", text)
    assert "bf16[1,4096,4,7,128]" not in text


# -- glm-5.2 on the serving path (PR 35): published widths, a published layer ----

def _glm(pattern, kinds):
    """One chip's share of ``benchmarks/configs/glm-5.2.json`` cut to
    ``pattern`` (shapes alone: no weight is made)."""
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                           "configs", "glm-5.2.json")) as f:
        config = json.load(f)
    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in config["model"].items()}
    kw.update(layer_pattern=pattern, indexer_types=kinds)
    model = GPTModel(GPTConfig(**kw))
    return config, model, jax.eval_shape(model.init_params,
                                         jax.random.PRNGKey(0))


def _glm_pools(model, blocks, block_size):
    return tuple(jax.ShapeDtypeStruct(
        (blocks, *spec[:2], block_size, spec[2]), *spec[3:] or (jnp.bfloat16,))
        for spec in model.cache_record())


def _metric_pattern(name):
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                           "metrics", name + ".json")) as f:
        return json.load(f)["args"]["pattern"]


def test_glm_tick_gathers_the_selected_records_and_no_other(v5e_devices):
    """The cell's tick (8 slots, 2 049 blocks of 64, both pools donated)
    over a full and a shared layer at the published widths: each layer's
    attention reads 8 x 2 048 records of 640 by one gather, the one indexer
    reads its keys by whole blocks, and the pools are updated in place."""
    config, model, params = _glm("*E*E", ("full", "shared"))
    slots, bs = config["engine"]["max_slots"], config["engine"]["block_size"]
    blocks = model.cfg.max_seq_len // bs
    pools = _glm_pools(model, 1 + slots * blocks, bs)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32)
    tables = jax.ShapeDtypeStruct((slots, blocks), jnp.int32)
    compiled = _compile(model.decode_step_paged,
                        (params, ints, pools, tables, ints),
                        SingleDeviceSharding(v5e_devices[0]),
                        donate_argnums=(2,))
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 0.25 * pool_bytes
    gathers = re.findall(r"= (\w+\[[\d,]+\])\S* gather\(", compiled.as_text())
    assert gathers.count("bf16[8,2048,640]") == 2       # a layer, selected
    assert gathers.count("f32[8,256,64,128]") == 1      # the indexer's keys
    assert not [g for g in gathers if g.startswith("bf16[8,16384")]
    pattern = _metric_pattern("grouped_dot_time_share.tpot")
    assert [l for l in compiled.as_text().splitlines()
            if re.search(pattern, l.strip())]


@pytest.mark.parametrize("bucket", [4096, 8192, 16384])
def test_masked_flash_compiles_for_v5e(bucket, v5e_devices):
    """A prefill's masked attention for one group of heads at the published
    widths (16 heads, q and k 256 wide, v 256), through the entry the model
    calls: one Mosaic call that takes ``(1, 16, bucket, 256)`` operands and
    the mask as int8, returns ``(1, bucket, 4096)`` rows, and leaves no
    float32 scores in HBM."""
    from apex_tpu.models.gpt import _PREFILL_HEADS
    from apex_tpu.ops.latent_attention import masked_attention
    heads = jax.ShapeDtypeStruct((1, _PREFILL_HEADS, bucket, 256),
                                 jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((1, bucket, bucket), jnp.bool_)
    compiled = _compile(
        lambda q, k, v, m: masked_attention(q, k, v, m, 256 ** -0.5),
        (heads, heads, heads, mask), SingleDeviceSharding(v5e_devices[0]))
    calls = [c.removeprefix("ROOT ") for c in _custom_calls(compiled)]
    assert len(calls) == 1 and re.search(
        _metric_pattern("masked_flash_time_share.ttft"), calls[0]), calls
    assert f"= bf16[1,{bucket},4096]" in calls[0]
    assert f"s8[1,{bucket},{bucket}]" in calls[0]
    assert not re.search(r"= \(?f32\[\d+,\d+,%d\]" % bucket,
                         compiled.as_text())


def test_masked_flash_halves_its_row_block_past_16384_keys(v5e_devices):
    """A row block's strip of the mask is held twice in VMEM at rows x
    keys bytes each: at 32 768 keys the row block is 512, by the shape
    alone, and the kernel still fits."""
    from apex_tpu.ops.latent_attention import masked_attention
    heads = jax.ShapeDtypeStruct((1, 2, 32768, 256), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((1, 32768, 32768), jnp.bool_)

    def attend(q, k, v, m):
        return masked_attention(q, k, v, m, 256 ** -0.5)
    grids = [eqn.params["grid_mapping"].grid for eqn in _pallas_calls(
        jax.make_jaxpr(attend)(heads, heads, heads, mask).jaxpr)]
    assert grids == [(1, 64, 2, 32)]
    _compile(attend, (heads, heads, heads, mask),
             SingleDeviceSharding(v5e_devices[0]))


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_glm_prefill_holds_a_group_of_heads_at_a_time(bucket, v5e_devices):
    """A full attention layer's prefill at the published widths: the
    expanded q, k, v alive at once are 16 heads', 1.8 GB of temporaries at
    16 384 positions where all 64 heads' (and their float32 scores) were
    4.8.  Since PR 36 the scores never reach HBM: attention is the Mosaic
    call ``%masked_flash`` (the name ``masked_flash_time_share.ttft`` reads)
    and no operation returns float32 ``[16,128,keys]`` or bf16
    ``[1,1,128,16,256]``, the shapes ``sparse_attention_time_share.ttft``
    found the loop's by."""
    _, model, params = _glm("*", ("full",))
    compiled = jax.jit(model.prefill).lower(*_abstract(
        (params, jax.ShapeDtypeStruct((1, bucket), jnp.int32)),
        SingleDeviceSharding(v5e_devices[0]))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2.0e9 * bucket / 16384 + 0.3e9
    lines = [l.strip() for l in compiled.as_text().splitlines()]
    kernel = _metric_pattern("masked_flash_time_share.ttft")
    assert [l for l in lines if re.search(kernel, l)]
    old = _metric_pattern("sparse_attention_time_share.ttft")
    assert not [l for l in lines if re.search(old, l)]


def test_glm_context_write_updates_both_pools_in_place(v5e_devices):
    from apex_tpu.serving.paged_kv import scatter_context_kv
    pools = (jax.ShapeDtypeStruct((2049, 5, 1, 64, 640), jnp.bfloat16),
             jax.ShapeDtypeStruct((2049, 2, 1, 64, 128), jnp.float32))
    records = (jax.ShapeDtypeStruct((5, 1, 1, 8192, 1, 640), jnp.bfloat16),
               jax.ShapeDtypeStruct((2, 1, 1, 8192, 1, 128), jnp.float32))
    compiled = _pool_program(
        scatter_context_kv,
        (pools, records, jax.ShapeDtypeStruct((128,), jnp.int32),
         jax.ShapeDtypeStruct((), jnp.int32)), v5e_devices[0])
    pool_bytes = sum(int(np.prod(p.shape)) * p.dtype.itemsize for p in pools)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pool_bytes
    assert memory.temp_size_in_bytes < 0.25 * pool_bytes


@pytest.mark.slow
@pytest.mark.parametrize("program", ["tick", "prefill"])
def test_glm_programs_compile_at_the_cells_depth(program, v5e_devices):
    """The whole share (7.78 GB of bf16 weights): the tick, and the prefill
    at bucket 16 384, which with the pool's 0.91 GB must leave the chip's
    16 GB room."""
    config, model, params = _glm(
        "*D*E*E*E*E", ("full", "shared", "shared", "shared", "full"))
    one = SingleDeviceSharding(v5e_devices[0])
    if program == "prefill":
        compiled = jax.jit(model.prefill).lower(*_abstract(
            (params, jax.ShapeDtypeStruct((1, 16384), jnp.int32)),
            one)).compile()
        memory = compiled.memory_analysis()
        assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
                + memory.output_size_in_bytes) < 11.5e9
        return
    pools = _glm_pools(model, 2049, 64)
    ints = jax.ShapeDtypeStruct((8,), jnp.int32)
    _compile(model.decode_step_paged,
             (params, ints, pools, jax.ShapeDtypeStruct((8, 256), jnp.int32),
              ints), one, donate_argnums=(2,))


# -- command-a-plus-05-2026: grouped heads, a window, the parallel block ---------

def _command_a(pattern="PPPP"):
    """``(config, model, abstract params)`` of the cell's share, ``pattern``
    parallel layers deep (window layers first, the last one full)."""
    with open(os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                           "configs", "command-a-plus-05-2026.json")) as f:
        config = json.load(f)
    kw = {k: getattr(jnp, v) if v in ("bfloat16", "float32") else v
          for k, v in config["model"].items()}
    kw.update(layer_pattern=pattern, attention_types=["sliding"] * (
        len(pattern) - 1) + ["full"])
    model = GPTModel(GPTConfig(**kw))
    return config, model, jax.eval_shape(model.init_params,
                                         jax.random.PRNGKey(0))


def _kernel_operands(compiled, metric):
    """The operand shapes of every Mosaic call the metric's pattern finds,
    as the call's layout constraints list them."""
    return [re.findall(r"(\w+\[[\d,]*\])\{", c.split(
        "operand_layout_constraints={", 1)[1].split("}}", 1)[0] + "}")
        for c in (c.removeprefix("ROOT ") for c in _custom_calls(compiled))
        if re.search(_metric_pattern(metric), c)]


@pytest.mark.parametrize("bucket", [1024, 16384])
@pytest.mark.parametrize("window", [4096, None])
def test_window_flash_compiles_for_v5e(bucket, window, v5e_devices):
    """A prefill's attention at the published widths (rows of 128 query
    heads and 8 KV heads of 128), window and full, through the entry the
    model calls: one Mosaic call named as the metric finds it, that takes
    K and V as the 1 024-lane rows of 8 heads: nothing broadcasts them to
    the 128 query heads."""
    from apex_tpu.ops.flash_attention import grouped_attention
    q = jax.ShapeDtypeStruct((1, bucket, 128 * 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, bucket, 8 * 128), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: grouped_attention(q, k, v, 128, window),
        (q, kv, kv), SingleDeviceSharding(v5e_devices[0]))
    assert len(_custom_calls(compiled)) == 1
    assert _kernel_operands(compiled, "window_flash_time_share.ttft") == [
        [f"bf16[1,{bucket},16384]"] + [f"bf16[1,{bucket},1024]"] * 2]


def test_the_grouped_tick_reads_both_stores_in_place(v5e_devices):
    """The cell's tick over one window and one full layer at the published
    widths (16 slots, blocks of 64): one ``%grouped_decode`` call a layer,
    each reading the store of its kind as it lies (K and V of 8 heads: no
    broadcast to the 128 query heads), both stores aliased (updated in
    place) and nothing of their size copied."""
    from apex_tpu.inference.engine import _picking
    config, model, params = _command_a("PP")
    one = SingleDeviceSharding(v5e_devices[0])
    pools = (jax.ShapeDtypeStruct((4097, 1, 2, 64, 1024), jnp.bfloat16),
             jax.ShapeDtypeStruct((1041, 1, 2, 64, 1024), jnp.bfloat16))
    ints = jax.ShapeDtypeStruct((16,), jnp.int32)
    tables = jax.ShapeDtypeStruct((16, 256), jnp.int32)
    compiled = _compile(_picking(model.decode_step_paged),
                        (params, ints, pools, (tables, tables), ints), one,
                        donate_argnums=(2,))
    stores = [operands[-1] for operands in _kernel_operands(
        compiled, "grouped_decode_time_share.tpot")]
    assert sorted(stores) == ["bf16[1041,1,2,64,1024]",
                              "bf16[4097,1,2,64,1024]"]
    text = compiled.as_text()
    assert not re.search(r"= bf16\[(4097|1041),1,2,64,1024\][^ ]* copy\(",
                         text)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= (4097 + 1041) * 2 * 64 * 1024 * 2


@pytest.mark.slow
def test_command_a_prefill_fits_beside_its_stores(v5e_devices):
    """The whole share (6.25 GB of bf16 weights) at bucket 16 384: the
    prefill with the pool's 1.07 GB and the window storage's 0.82 GB must
    leave the chip's 16 GB room; each layer's kernel takes K and V of 8
    heads."""
    config, model, params = _command_a()
    compiled = jax.jit(model.prefill).lower(*_abstract(
        (params, jax.ShapeDtypeStruct((1, 16384), jnp.int32)),
        SingleDeviceSharding(v5e_devices[0]))).compile()
    memory = compiled.memory_analysis()
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes + 1.9e9) < 13e9
    assert _kernel_operands(compiled, "window_flash_time_share.ttft") == [
        ["bf16[1,16384,16384]"] + ["bf16[1,16384,1024]"] * 2] * 4
