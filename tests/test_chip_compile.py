"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: ``jax.jit(...).lower(...).compile()`` against a ``v5e:2x2``
topology description makes Mosaic and the TPU compiler accept or refuse
each kernel exactly as they would on the chip — tile alignment of every
block, VMEM limits, program size — at the Table-2 published domains with
the planner's own depth and tile.  The interpreter hides all of that, so
these compiles are the guard between the CPU tests and a chip run.

The topology is described inside a module-scoped fixture (only the worker
that runs this file loads the TPU compiler library), and the persistent
compile cache is off while the fixture is alive: a compile for a
described chip is written to the cache but cannot be read back without
one.
"""
import math
import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import telemetry
from repro.api import compile_stencil
from repro.core.stencil_spec import get

# A compile is seconds; this bound only catches a kernel whose generated
# code has started to grow with the schedule again.
COMPILE_SECONDS = 30.0


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    seconds = time.perf_counter() - t0
    assert seconds < COMPILE_SECONDS, seconds
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name", ["j2d5pt", "j2d9pt", "j3d7pt", "j3d13pt"])
@pytest.mark.parametrize("path", ["apply", "run"])
def test_stencil_sweep_compiles_for_v5e(one_chip, name, path):
    """One sweep (``apply``) and the multi-sweep chain of ``run`` at the
    published domain, planner's ``t`` and tile, lowered by Mosaic."""
    spec = get(name)
    prog = compile_stencil(spec, spec.domain, interpret=False)
    assert prog.interpret is False and prog.t == prog.plan.t
    x = jax.ShapeDtypeStruct(spec.domain, jnp.float32, sharding=one_chip)
    fn = (lambda v: prog.apply(v)) if path == "apply" else \
        prog._run_fn(4 * prog.t)
    mem = _compile(fn, x).memory_analysis()
    # the field in HBM, its minor dim padded to the 128-lane tile
    *lead, minor = spec.domain
    field = 4 * math.prod(lead) * (-(-minor // 128) * 128)
    assert mem.argument_size_in_bytes == field
    assert mem.output_size_in_bytes == field


@pytest.mark.parametrize("name", ["j2d5pt", "j3d7pt"])
def test_op_phases_name_the_chain_for_v5e(one_chip, name):
    """The phases of the campaign's runner (120 steps) at the published
    domain, read as ``StencilProgram.op_phases`` reads them, from the
    compile for the described chip: every sweep launch in
    ``stencil.sweep`` under the kernel's stable name, and the pad and the
    crop, where XLA keeps them, in theirs."""
    spec = get(name)
    prog = compile_stencil(spec, spec.domain, interpret=False)
    x = jax.ShapeDtypeStruct(spec.domain, jnp.float32, sharding=one_chip)
    phases = telemetry.phases(_compile(prog._run_fn(120), x).as_text())
    kernel = f"ebisu{spec.ndim}d_t{prog.t}"
    sweep = [op for op, phase in phases.items() if phase == "stencil.sweep"]
    assert len(sweep) == 120 // prog.t
    assert all(op.rpartition(".")[0] == kernel for op in sweep)
    others = {op.rpartition(".")[0]: phase for op, phase in phases.items()
              if phase != "stencil.sweep"}
    # j2d5pt's domain is padded to the tile; j3d7pt's already fits it
    assert others == ({"pad": "stencil.pad", "slice": "stencil.crop"}
                      if name == "j2d5pt" else {})


@pytest.fixture(scope="module")
def danube_heads():
    """h2o-danube-1.8b's attention shape at sequence 4096, in bf16."""
    from repro.configs.h2o_danube_1p8b import CONFIG as cfg
    return 1, 4096, cfg.n_heads, cfg.kv_heads, cfg.head_dim


def test_flash_forward_compiles_for_v5e(one_chip, danube_heads):
    from repro.kernels.flash_attention import flash_attention_pallas

    b, s, h, kv, hd = danube_heads
    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, hd), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_pallas(q, k, v,
                                                    interpret=False),
             q, k, k)


def test_flash_backward_compiles_for_v5e(one_chip, danube_heads):
    from repro.kernels.flash_attention import flash_attention_trainable

    b, s, h, kv, hd = danube_heads
    q = jax.ShapeDtypeStruct((b, s, h, hd), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, hd), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention_trainable(q, k, v, True, None, 256, 512,
                                        False)
        return out.astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), q, k, k)
