"""The three named Mosaic flash calls, compiled ahead of time for a v5e at the
shapes of the benchmark's flash cell (4 clients vmapped over batch 8 x 12
heads, T 2,048, D 64, bf16, blocks 128/128). Nothing runs: the TPU's compiler
works against a described chip. The only file that describes a topology;
the description happens inside a module-scoped fixture, never at import."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fl4health_tpu.kernels.flash_attention import flash_attention

CLIENTS, BATCH, SEQ, HEADS, HEAD_DIM = 4, 8, 2048, 12, 64
ROWS = f"bf16[{CLIENTS},{BATCH * HEADS},{SEQ},{HEAD_DIM}]"
# kernel name -> the result types its custom call must have
KERNELS = {
    "flash_fwd": (ROWS, f"f32[{CLIENTS},{BATCH * HEADS},{SEQ},1]"),
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


@pytest.fixture(scope="module")
def mosaic_calls(one_chip):
    """name of the kernel -> its custom-call instruction in the HLO of the
    compiled forward or backward program."""
    from jax.experimental.compilation_cache import compilation_cache

    def attend(q, k, v, mask):
        return flash_attention(q, k, v, mask, 128, 128, interpret=False)

    def loss(q, k, v, mask):
        return jnp.sum(jax.vmap(attend)(q, k, v, mask).astype(jnp.float32))

    qkv = jax.ShapeDtypeStruct((CLIENTS, BATCH, SEQ, HEADS, HEAD_DIM),
                               jnp.bfloat16, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((CLIENTS, BATCH, SEQ), jnp.float32,
                                sharding=one_chip)
    # an executable compiled for a described chip cannot be read back from
    # the persistent cache without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        texts = [jax.jit(jax.vmap(attend)).lower(qkv, qkv, qkv, mask)
                 .compile().as_text(),
                 jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
                 .lower(qkv, qkv, qkv, mask).compile().as_text()]
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
