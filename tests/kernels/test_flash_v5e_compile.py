"""The named Mosaic calls of the benchmark's cells, compiled ahead of time for
a v5e: the three flash calls at the flash cell's shapes (4 clients vmapped
over batch 8 x 12 heads, T 2,048, D 64, bf16, blocks 128/128: lane-indexed,
two heads of 64 packed in a 128-lane block of ``[.., T, 768]``, and read by
the benchmark's ``flash_common.classify`` as 32 rows of 768), the same three
causal over one shared key/value head at the adapter cell's (20 heads of 128,
blocks 512/512: lane-indexed, the shared head's gradients one float32 array a
client), the same three causal over latent attention's parts (128 heads; q
and k as 128 lanes without positions and 64 rotary, the rotary key ONE head;
v 128, T 1,024, a static scale: lane-indexed, nothing padded to 256), the
same three causal over grouped key/value heads at the Nemotron-H cell's (32
query heads of 128 over 2), the same three under a sliding window at the
trinity_mini cell's (32 query heads of 128 over 4, T 8,192, a window of
2,048), the
adapter cell's two selective-scan calls (4 clients x 2,048
positions x 5,120 channels x 16 states), and the Nemotron-H cell's two
chunked scalar-decay scan calls (4 clients x 2,048 positions x 128 heads of
64 in 8 groups x state 128, chunks of 128), and the routed layer's combine
at the trinity_mini cell's (a chunk's 4,096 rows of 16 x 128 float32 lanes
added into 32,768, ``kernels/row_combine.py``). Nothing runs: the TPU's compiler
works against a described chip. The only file that describes a topology;
the description happens inside a module-scoped fixture, never at import."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.harness.spec import load_module
from fl4health_tpu.kernels import row_combine
from fl4health_tpu.kernels import ssd_scan as ssd
from fl4health_tpu.kernels.flash_attention import flash_attention
from fl4health_tpu.kernels.selective_scan import (BLOCK_T, UNROLL,
                                                  _blocked_scan)

CLIENTS, BATCH, SEQ, HEADS, HEAD_DIM = 4, 8, 2048, 12, 64
# q, k, v, the output and the three gradients lie as the projections hold
# them, [.., T, 12 * 64]; the per-row statistic a head, [.., 12, T, 1]
ROWS = f"bf16[{CLIENTS},{BATCH},{SEQ},{HEADS * HEAD_DIM}]"
# kernel name -> the result types its custom call must have
KERNELS = {
    "flash_fwd": (ROWS, f"f32[{CLIENTS},{BATCH},{HEADS},{SEQ},1]"),
    "flash_dq": (ROWS,),
    "flash_dkv": (ROWS, ROWS),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  whatever the plugin raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _text_as_a_trace_names_ops(compiled):
    """The optimised HLO with each operand's shape before its name: the form
    in which a profile names a device op, which the benchmark's readers
    parse (``as_text()`` leaves the operands' shapes out)."""
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_operand_shape = True
    return compiled.runtime_executable().hlo_modules()[0].to_string(options)


def _compiled_calls(one_chip, clients, batch, seq, heads, kv_heads, head_dim,
                    block, causal, v_dim=None, scale=None, shared_part=None,
                    window=None):
    """name of the kernel -> its custom-call instructions in the HLO of the
    compiled forward and backward programs. ``shared_part``: q and k are two
    parts, ``head_dim`` wide with ``kv_heads`` heads and ``shared_part`` wide
    with ONE key head."""
    from jax.experimental.compilation_cache import compilation_cache

    def attend(q, k, v, mask):
        return flash_attention(q, k, v, mask, block, block, interpret=False,
                               causal=causal, scale=scale, window=window)

    def loss(q, k, v, mask):
        return jnp.sum(jax.vmap(attend)(q, k, v, mask).astype(jnp.float32))

    def arg(n_heads, width):
        return jax.ShapeDtypeStruct((clients, batch, seq, n_heads, width),
                                    jnp.bfloat16, sharding=one_chip)

    q, kv = arg(heads, head_dim), arg(kv_heads, head_dim)
    if shared_part:
        q, kv = (q, arg(heads, shared_part)), (kv, arg(1, shared_part))
    v = arg(kv_heads, v_dim or head_dim)
    mask = jax.ShapeDtypeStruct((clients, batch, seq), jnp.float32,
                                sharding=one_chip)
    # an executable compiled for a described chip cannot be read back from
    # the persistent cache without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        texts = [_text_as_a_trace_names_ops(
            jax.jit(fn).lower(q, kv, v, mask).compile())
            for fn in (jax.vmap(attend), jax.grad(loss, argnums=(0, 1, 2)))]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    calls = {}
    for text in texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            for name in KERNELS:
                # jvp/transpose/vmap wrap the name; nothing else holds it
                if re.search(rf"%\w*{name}_*[.\d]* = ", line):
                    calls.setdefault(name, []).append(line)
    return calls


@pytest.fixture(scope="module")
def mosaic_calls(one_chip):
    return _compiled_calls(one_chip, CLIENTS, BATCH, SEQ, HEADS, HEADS,
                           HEAD_DIM, 128, causal=False)


# the adapter cell's attention layer: 4 clients x batch 1 x 20 query heads of
# 128 over ONE key/value head, T 2,048, causal, blocks 512/512. Lane-indexed:
# q, the output and dQ are [.., T, 20 * 128] as the projections hold them, the
# per-row statistic [.., 20, T, 1]; dK and dV of the shared head leave the
# call summed over the 20 heads in float32, one [T, 128] array a client
CAUSAL_ROWS, CAUSAL_SHARED = "bf16[4,1,2048,2560]", "f32[4,1,2048,128]"
CAUSAL_KERNELS = {
    "flash_fwd": (CAUSAL_ROWS, "f32[4,1,20,2048,1]"),
    "flash_dq": (CAUSAL_ROWS,),
    "flash_dkv": (CAUSAL_SHARED, CAUSAL_SHARED),
}


@pytest.fixture(scope="module")
def causal_calls(one_chip):
    return _compiled_calls(one_chip, 4, 1, 2048, 20, 1, 128, 512, causal=True)


def _result_shapes(line):
    result = line.split(" = ", 1)[1].split(" custom-call(", 1)[0]
    return tuple(re.sub(r"\{[^}]*\}", "", s) for s in
                 re.findall(r"(?:bf16|f32)\[[\d,]+\](?:\{[^}]*\})?", result))


@pytest.mark.parametrize("name", sorted(CAUSAL_KERNELS))
def test_causal_shared_head_call_compiles_for_the_v5e(causal_calls, name):
    lines = causal_calls.get(name)
    assert lines, f"no tpu_custom_call named {name}: {sorted(causal_calls)}"
    for line in lines:
        assert _result_shapes(line) == CAUSAL_KERNELS[name], (name, line)
    assert len(lines) == (2 if name == "flash_fwd" else 1)


# the Nemotron-H cell's attention block: 4 clients x batch 1 x 32 query heads
# of 128 over TWO key/value heads, T 2,048, causal, blocks 512/512.
# Lane-indexed with grouped key/value heads: q, the output and dQ are [.., T,
# 32 * 128], k / v [.., T, 2 * 128] read at lane block h // 16; dK and dV
# leave the call summed over each group's 16 heads in float32
GQA_ROWS, GQA_KV = "bf16[4,1,2048,4096]", "f32[4,1,2048,256]"
GQA_KERNELS = {
    "flash_fwd": (GQA_ROWS, "f32[4,1,32,2048,1]"),
    "flash_dq": (GQA_ROWS,),
    "flash_dkv": (GQA_KV, GQA_KV),
}


@pytest.fixture(scope="module")
def gqa_calls(one_chip):
    return _compiled_calls(one_chip, 4, 1, 2048, 32, 2, 128, 512, causal=True)


@pytest.mark.parametrize("name", sorted(GQA_KERNELS))
def test_grouped_heads_call_compiles_for_the_v5e(gqa_calls, name):
    lines = gqa_calls.get(name)
    assert lines, f"no tpu_custom_call named {name}: {sorted(gqa_calls)}"
    for line in lines:
        assert _result_shapes(line) == GQA_KERNELS[name], (name, line[:400])
    assert len(lines) == (2 if name == "flash_fwd" else 1)


# the trinity_mini cell's sliding layers: 4 clients x batch 1 x 32 query heads
# of 128 over FOUR key/value heads, T 8,192, causal under a window of 2,048,
# blocks 512/512 (70 of the causal 136 tiles a head are executed: a query
# block walks its live range alone, five key blocks as one straight-line body
# past the first window). K / V of one key head whole in VMEM are 4 MiB,
# inside ``_check_compilable``'s 8; the causal calls ask for a scoped limit of
# 32 MiB (``_CAUSAL_PARAMS``: dQ's two forms of the loop passed the default
# 16 by 160 kB at this shape)
WINDOW_ROWS, WINDOW_KV = "bf16[4,1,8192,4096]", "f32[4,1,8192,512]"
WINDOW_KERNELS = {
    "flash_fwd": (WINDOW_ROWS, "f32[4,1,32,8192,1]"),
    "flash_dq": (WINDOW_ROWS,),
    "flash_dkv": (WINDOW_KV, WINDOW_KV),
}


@pytest.fixture(scope="module")
def window_calls(one_chip):
    return _compiled_calls(one_chip, 4, 1, 8192, 32, 4, 128, 512, causal=True,
                           window=2048)


@pytest.mark.parametrize("name", sorted(WINDOW_KERNELS))
def test_window_call_compiles_for_the_v5e(window_calls, name):
    lines = window_calls.get(name)
    assert lines, f"no tpu_custom_call named {name}: {sorted(window_calls)}"
    for line in lines:
        assert _result_shapes(line) == WINDOW_KERNELS[name], (name, line[:400])
    assert len(lines) == (2 if name == "flash_fwd" else 1)


# the trinity_mini cell's full layers: the same operands, causal with no
# window: 136 tiles a head, a query block's interior key blocks in whole
# trips of four under a trip count of the grid index
@pytest.fixture(scope="module")
def full_calls(one_chip):
    return _compiled_calls(one_chip, 4, 1, 8192, 32, 4, 128, 512, causal=True)


@pytest.mark.parametrize("name", sorted(WINDOW_KERNELS))
def test_full_layer_call_compiles_for_the_v5e(full_calls, name):
    lines = full_calls.get(name)
    assert lines, f"no tpu_custom_call named {name}: {sorted(full_calls)}"
    for line in lines:
        assert _result_shapes(line) == WINDOW_KERNELS[name], (name, line[:400])
    assert len(lines) == (2 if name == "flash_fwd" else 1)


# latent attention of the expert cell: 4 clients x batch 1 x 128 heads, q / k
# as two parts (128 lanes without positions; 64 rotary, the key's ONE head),
# v 128, T 1,024, causal, blocks 512/512, the softmax scale with YaRN's mscale
# in it. Lane-indexed: no row is 256 wide; the rotary part of q and its
# gradient lie [.., 128 heads, T, 64]; the rotary key's gradient is ONE
# float32 [T, 64] array a client, summed over the heads inside the call
MLA_ROWS = "bf16[4,1,1024,16384]"
MLA_KERNELS = {
    "flash_fwd": (MLA_ROWS, "f32[4,1,128,1024,1]"),
    "flash_dq": (MLA_ROWS, "bf16[4,1,128,1024,64]"),
    "flash_dkv": (MLA_ROWS, "f32[4,1,1024,64]", MLA_ROWS),
}


@pytest.fixture(scope="module")
def mla_calls(one_chip):
    return _compiled_calls(one_chip, 4, 1, 1024, 128, 128, 128, 512,
                           causal=True, scale=0.114721, shared_part=64)


@pytest.mark.parametrize("name", sorted(MLA_KERNELS))
def test_two_part_call_compiles_for_the_v5e(mla_calls, name):
    lines = mla_calls.get(name)
    assert lines, f"no tpu_custom_call named {name}: {sorted(mla_calls)}"
    for line in lines:
        assert _result_shapes(line) == MLA_KERNELS[name], (name, line[:400])
    assert len(lines) == (2 if name == "flash_fwd" else 1)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_named_flash_call_compiles_for_the_v5e(mosaic_calls, name):
    lines = mosaic_calls.get(name)
    assert lines, f"no tpu_custom_call named {name}: {sorted(mosaic_calls)}"
    for line in lines:
        result = line.split(" = ", 1)[1].split(" custom-call(", 1)[0]
        shapes = tuple(re.sub(r"\{[^}]*\}", "", s) for s in
                       re.findall(r"(?:bf16|f32)\[[\d,]+\](?:\{[^}]*\})?", result))
        assert shapes == KERNELS[name], (name, result)
    # the forward runs once in the forward program and once under grad
    assert len(lines) == (2 if name == "flash_fwd" else 1)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_the_benchmarks_reader_knows_the_flash_cells_calls(mosaic_calls,
                                                           name):
    """``flash_ms_per_round`` / ``flash_roofline_pct`` find the flash calls
    by their signature: the HLO line of each compiled call, as a trace names
    the op, classifies as its kind over clients x batch = 32 rows of T 2,048
    x 768 = 12 heads x 64 lanes of bf16. ``flops/flash_attention.py`` is
    linear in rows x d, so 32 x 768 counts what 384 x 64 did."""
    flash_common = load_module("layer_metrics", "flash_common")
    for line in mosaic_calls[name]:
        assert flash_common.classify(line.strip()) == (
            name.removeprefix("flash_"), CLIENTS * BATCH, SEQ,
            HEADS * HEAD_DIM, 2), line[:400]


# the adapter cell's Mamba mixers: 4 clients x batch 1 x 2,048 positions x
# 5,120 channels (8 channel blocks of 640) x 16 states, bf16 operands
SCAN_ROWS = "bf16[4,1,2048,5120]"
SCAN_KERNELS = {
    # y and the states at the starts of the 32 time blocks
    "ssm_scan_fwd": (SCAN_ROWS, "f32[4,1,32,16,5120]"),
    # dx, dDelta, dz, the per-lane partial sums of dB and dC, dA, dD. The
    # partial sums carry NO channel-block axis: the kernel sums the 8 channel
    # blocks of a time block in VMEM, so what leaves the chip is B's own
    # lane-splat size, 67 MB, and not 537 (f32[4,1,8,2048,16,128])
    "ssm_scan_bwd": (SCAN_ROWS, SCAN_ROWS, SCAN_ROWS,
                     "f32[4,1,2048,16,128]", "f32[4,1,2048,16,128]",
                     "f32[4,1,16,5120]", "f32[4,1,1,5120]"),
}


@pytest.fixture(scope="module")
def scan_calls(one_chip):
    from jax.experimental.compilation_cache import compilation_cache

    def scan(x, dt, a, b, c, d, z):
        return _blocked_scan(x, dt, a, b, c, d, z, BLOCK_T, UNROLL, False)

    clients = jax.vmap(scan, in_axes=(0, 0, None, 0, 0, None, 0))

    def loss(*ops):
        return jnp.sum(clients(*ops).astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    seq, state = (4, 1, 2048, 5120), (4, 1, 2048, 16)
    ops = (arg(seq, jnp.bfloat16), arg(seq, jnp.bfloat16),
           arg((5120, 16), jnp.float32), arg(state, jnp.float32),
           arg(state, jnp.float32), arg((5120,), jnp.float32),
           arg(seq, jnp.bfloat16))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(jax.grad(loss, argnums=tuple(range(7)))).lower(
            *ops).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    calls = {}
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for name in SCAN_KERNELS:
            if re.search(rf"%\w*{name}_*[.\d]* = ", line):
                calls.setdefault(name, []).append(line)
    return calls


@pytest.mark.parametrize("name", sorted(SCAN_KERNELS))
def test_selective_scan_call_compiles_for_the_v5e(scan_calls, name):
    lines = scan_calls.get(name)
    assert lines and len(lines) == 1, (name, sorted(scan_calls))
    assert _result_shapes(lines[0]) == SCAN_KERNELS[name], lines[0][:400]


# the Nemotron-H cell's Mamba-2 blocks: 4 clients x batch 1 x 2,048 positions
# x 128 heads of 64 in 8 groups (a group's 16 heads are 1,024 lanes of the
# model's [.., T, 8,192]) x state 128, chunks of 128, bf16 operands
SSD_X, SSD_BC = "bf16[4,1,2048,8192]", "bf16[4,1,2048,1024]"
SSD_COLS = "f32[4,1,8,2048,16]"  # a group's heads side by side, a position a row
SSD_KERNELS = {
    # y, and under differentiation the states at the 16 chunks' starts
    "ssd_chunk_fwd": ("f32[4,1,2048,8192]", "f32[4,1,16,8,128,1024]"),
    # dx, dB, dC (a group's 16 heads summed inside the call), d(dt), d(cum)
    # as columns, d(cum) as rows
    "ssd_chunk_bwd": (SSD_X, SSD_BC, SSD_BC, SSD_COLS, SSD_COLS,
                      "f32[4,1,16,8,16,128]"),
}
# a [128, 128] tile a (client, chunk, head): what the jnp form's decay,
# scores and their cotangents are (537 MB of float32)
SSD_TILES = 4 * 16 * 128 * 128 * 128


@pytest.fixture(scope="module")
def ssd_texts(one_chip):
    """The compiled forward, forward + gradient and, for comparison, the
    ``jnp`` form's forward."""
    from jax.experimental.compilation_cache import compilation_cache

    def clients(scan):
        return jax.vmap(lambda x, dt, a, b, c: scan(x, dt, a, b, c, 128),
                        in_axes=(0, 0, None, 0, 0))

    def loss(*ops):
        return jnp.sum(clients(ssd.ssd_scan)(*ops))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ops = (arg((4, 1, 2048, 128, 64), jnp.bfloat16),
           arg((4, 1, 2048, 128), jnp.float32), arg((128,), jnp.float32),
           arg((4, 1, 2048, 8, 128), jnp.bfloat16),
           arg((4, 1, 2048, 8, 128), jnp.bfloat16))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # the public entry asks the platform (the CPU here) whether to interpret
    steer = pytest.MonkeyPatch()
    steer.setattr(ssd, "interpret_default", lambda: False)
    try:
        return {name: jax.jit(fn).lower(*ops).compile().as_text()
                for name, fn in (
                    ("forward", clients(ssd.ssd_scan)),
                    ("gradient", jax.grad(loss, argnums=tuple(range(5)))),
                    ("jnp forward", clients(ssd.ssd_scan_xla)))}
    finally:
        steer.undo()
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def _ssd_calls(text, name):
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and re.search(rf"%\w*{name}_*[.\d]* = ", line)]


@pytest.mark.parametrize("name", sorted(SSD_KERNELS))
def test_chunked_scan_call_compiles_for_the_v5e(ssd_texts, name):
    (line,) = _ssd_calls(ssd_texts["gradient"], name)
    assert _result_shapes(line) == SSD_KERNELS[name], line[:400]
    # forward, recomputed forward and backward are told apart by JAX's own
    # markers in the name stack (benchmarks/layer_metrics/pass_common.py)
    op_name = re.search(r'op_name="([^"]*)"', line).group(1)
    assert "fl_layer::ssd_scan" in op_name, op_name
    assert ("transpose(" in op_name) == (name == "ssd_chunk_bwd"), op_name
    if name == "ssd_chunk_fwd":  # no gradient asked: no states written
        (line,) = _ssd_calls(ssd_texts["forward"], name)
        assert _result_shapes(line) == SSD_KERNELS[name][:1], line[:400]


def _tile_arrays(text):
    """The instructions of a compiled program, outside the Mosaic calls, whose
    result holds a [128, 128] tile a (client, chunk, head) or more."""
    found = []
    for line in text.splitlines():
        if " = " not in line or "tpu_custom_call" in line:
            continue
        result = line.split(" = ", 1)[1].split("(", 1)[0]
        for dims in re.findall(r"(?:bf16|f32|pred|s32)\[([\d,]+)\]", result):
            dims = [int(d) for d in dims.split(",")]
            n = 1
            for d in dims:
                n *= d
            if n >= SSD_TILES and dims[-2:] == [128, 128]:
                found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("program", ["forward", "gradient"])
def test_no_decay_tile_reaches_hbm(ssd_texts, program):
    """Forward, and forward + backward: no array of rank >= 2 whose last two
    axes are a chunk's positions twice (128, 128) times 4 clients x 16
    chunks x 128 heads exists outside a custom call, where the ``jnp`` form
    holds several (its forward's are found by the same reader)."""
    assert _tile_arrays(ssd_texts[program]) == []
    assert _tile_arrays(ssd_texts["jnp forward"])


def test_the_routed_layers_combine_compiles_for_the_v5e(one_chip):
    """``add_rows`` at the window cell's shape: one row copy in and one out
    an index, ``y`` updated in place (aliased: no second [32,768, 16, 128]
    among the temporaries)."""
    from jax.experimental.compilation_cache import compilation_cache

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    slab = row_combine.slab(2048)
    assert slab == (16, 128)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        compiled = jax.jit(
            lambda y, rows, updates: row_combine._add_rows_call(
                y, rows, updates, block=256, interpret=False),
            donate_argnums=0).lower(
                arg((32768, *slab), jnp.float32), arg((4096,), jnp.int32),
                arg((4096, *slab), jnp.float32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and re.search(r"%\w*add_rows[.\d]* = ", calls[0])
    assert "f32[32768,16,128]" in calls[0].split(" = ")[1].split("(")[0]
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == 32768 * 2048 * 4
    assert stats.temp_size_in_bytes < 1 << 20
