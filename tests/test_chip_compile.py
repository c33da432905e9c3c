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
one.  The compiler library is loaded with ``--xla_mosaic_dump_to``, so
every Mosaic kernel compiled here leaves its passes in a directory the
tests can count vector ops in.
"""
import collections
import math
import os
import re
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
def mosaic_dump(tmp_path_factory):
    """Where the TPU compiler writes each Mosaic kernel's passes."""
    return tmp_path_factory.mktemp("mosaic")


@pytest.fixture(scope="module")
def topo(mosaic_dump):
    """The described ``v5e:2x2`` host: four chips, none attached."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # read once, when the topology description loads the library
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(
        filter(None, [os.environ.get("LIBTPU_INIT_ARGS"),
                      f"--xla_mosaic_dump_to={mosaic_dump}"]))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    seconds = time.perf_counter() - t0
    assert seconds < COMPILE_SECONDS, seconds
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name", ["j2d5pt", "j2d9pt", "j2d9pt-gol",
                                  "j2d25pt", "j3d7pt", "j3d13pt"])
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


@pytest.mark.parametrize("name,domain", [("j2d5pt", (2048, 32768)),
                                         ("j2d9pt", (4096, 16384))])
def test_wide_2d_sweep_compiles_for_v5e(one_chip, name, domain):
    """Rows wide enough that a tall chunk's spills would outgrow VMEM:
    the sweep falls back to 8-row chunks and still lowers."""
    from repro.kernels.stencil2d import MARGIN, chunk_rows

    spec = get(name)
    assert chunk_rows(spec.taps, domain[1]) == MARGIN
    prog = compile_stencil(spec, domain, interpret=False)
    x = jax.ShapeDtypeStruct(domain, jnp.float32, sharding=one_chip)
    _compile(lambda v: prog.apply(v), x)


# `%12 = tpu.load %buf[...] : memref<...>, vector<8x128xf32>` -> "tpu.load"
_VECTOR_OP = re.compile(r"^\s*(?:%\S+ = )?([a-z_]+\.[a-z_]+)\b.*vector<")


def _mosaic_pass(mosaic_dump, kernel, name, compile_it):
    """The text of pass ``name`` in the Mosaic dump of the kernel
    ``compile_it`` compiles."""
    pattern = f"*-{kernel}-{name}.txt"
    before = set(mosaic_dump.glob(pattern))
    compile_it()
    (dump,) = set(mosaic_dump.glob(pattern)) - before
    return dump.read_text()


def _sweep_chunk_body(mosaic_dump, kernel, compile_it):
    """The vector ops, by name, of the loop body that applies the taps to
    the most rows, in the Mosaic dump of the kernel ``compile_it``
    compiles.  Each region counts only the ops directly inside it."""
    text = _mosaic_pass(mosaic_dump, kernel,
                        "post-apply-vector-layout-simplify", compile_it)
    stack, bodies = [collections.Counter()], []
    for line in text.splitlines():
        text = line.strip()
        if text.startswith("}"):
            bodies.append(stack.pop())
        op = _VECTOR_OP.match(line)
        if op and not text.startswith("}"):
            stack[-1][op.group(1)] += 1
        if text.endswith("{"):
            stack.append(collections.Counter())
    return max((b for b in bodies if b["tpu.dynamic_rotate"]),
               key=lambda b: b["tpu.store"])


def test_j2d5pt_launched_strip():
    """The strip the executor launches j2d5pt's campaign in on the chip:
    t=10, 272 rows with 16-row rims (304-row strips), 31 of them on an
    8432 x 8448 padded field."""
    from repro.api.program import _sweep_tile_2d
    from repro.core.roofline import TPU_V5E
    from repro.kernels.stencil2d import padded_shape_2d

    spec = get("j2d5pt")
    prog = compile_stencil(spec, spec.domain, interpret=False)
    assert prog.t == 10
    assert _sweep_tile_2d(spec, 10, spec.domain, TPU_V5E, prog.plan,
                          aligned=True) == 272
    assert padded_shape_2d(spec, 10, 272, *spec.domain,
                           aligned=True) == (8432, 8448)


@pytest.mark.parametrize("height,width,padded", [
    (8352, 8352, (8432, 8448)),   # j2d5pt's domain, as launched
    (1024, 1024, (1088, 1024)),   # a service shape, no pad lane
])
def test_j2d5pt_launch_compiles_in_tall_chunks_for_v5e(one_chip, mosaic_dump,
                                                       height, width, padded):
    """j2d5pt's sweep at the executor's strip for t=10: each step computes
    the strip in chunks of ``CHUNK`` rows, each loading its rows and one
    8-row margin per side, so it loads 4/3 of a vreg for each vreg it
    stores where 8-row chunks loaded 3."""
    from repro.kernels.stencil2d import CHUNK, ebisu2d_padded

    spec = get("j2d5pt")
    x = jax.ShapeDtypeStruct(padded, jnp.float32, sharding=one_chip)
    body = _sweep_chunk_body(mosaic_dump, "ebisu2d_t10", lambda: _compile(
        lambda v: ebisu2d_padded(v, spec, 10, height=height, width=width,
                                 bh=272, interpret=False), x))
    vregs = CHUNK // 8 * padded[1] // 128
    assert body["tpu.store"] == vregs
    assert body["tpu.load"] * CHUNK == vregs * (CHUNK + 16)


def test_j3d7pt_launch_streams_z_for_v5e(one_chip, mosaic_dump):
    """j3d7pt's sweep as its campaign launches it (t=8, z blocks of 40
    planes): the z steps of each in-plane column run last and in order
    (``arbitrary``: the queues carry across them), plus one step that
    drains the last ``halo`` planes; each step stages one 40-plane input
    block and no z rims.  Its VMEM limit is no larger than that of a
    launch staging ``zc + 2·halo`` input planes a step."""
    from repro.api.program import _sweep_tile_3d
    from repro.core.roofline import TPU_V5E
    from repro.kernels.stencil2d import vmem_limit
    from repro.kernels.stencil3d import ebisu3d_padded

    spec = get("j3d7pt")
    prog = compile_stencil(spec, spec.domain, interpret=False)
    zc, ty, tx, _ = _sweep_tile_3d(spec, 8, spec.domain, TPU_V5E, prog.plan,
                                   aligned=True)
    assert (zc, ty, tx) == (40, None, None)
    x = jax.ShapeDtypeStruct(spec.domain, jnp.float32, sharding=one_chip)

    def sweep(v):
        return ebisu3d_padded(v, spec, 8, zdim=2560, ydim=288, xdim=384,
                              zc=zc, interpret=False)

    main = _mosaic_pass(mosaic_dump, "ebisu3d_t8", "original",
                        lambda: _compile(sweep, x))
    main = next(line for line in main.splitlines()
                if "func.func @main" in line)
    assert ("dimension_semantics = [#tpu.dimension_semantics<parallel>, "
            "#tpu.dimension_semantics<parallel>, "
            "#tpu.dimension_semantics<arbitrary>]") in main
    assert "iteration_bounds = array<i64: 1, 1, 65>" in main
    # the input view, the output block, the t queue rings, the carry
    assert re.findall(r"memref<([0-9x]+)xf32", main) == [
        "40x288x384", "40x288x384", "8x4x304x384", "32x288x384"]
    # the VMEM limit, in the kernel's backend config
    (limit,) = re.findall(r"\\22size\\22: ([0-9]+)",
                          jax.jit(sweep).lower(x).as_text())
    plane, rings = 288 * 384, 8 * 4 * (288 + 16) * 384
    assert int(limit) <= vmem_limit(
        4 * (2 * (zc + 16 + zc) * plane + rings))


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


def test_sharded_kernel_path_compiles_for_v5e_2x2(topo):
    """j3d7pt-weak2x2: the 5120x576x384 field over a 2x2 mesh of the
    described host, one j3d7pt Table-2 domain a chip.  Every shard runs
    the 3-D sweep kernel (the planner's t=8 on the shard) between
    exchange rounds; a remainder block adds a launch of its own depth."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("shard0", "shard1"))
    prog = compile_stencil(get("j3d7pt"), (5120, 576, 384), mesh=mesh,
                           interpret=False)
    assert prog.t == 8
    total = prog.t + 1                       # blocks of depth 8 and 1
    x = jax.ShapeDtypeStruct(prog.shape, jnp.float32, sharding=NamedSharding(
        mesh, PartitionSpec("shard0", "shard1")))
    mem = _compile(prog._sharded_jit(total), x).memory_analysis()
    assert mem.argument_size_in_bytes == 4 * 2560 * 288 * 384   # a shard
    phases = prog.op_phases(total)
    sweep = sorted(op.rpartition(".")[0] for op, phase in phases.items()
                   if phase == "stencil.sweep")
    assert sweep == ["ebisu3d_t1", "ebisu3d_t8"]
    exchange = [op for op, phase in phases.items()
                if phase == "stencil.exchange"]
    assert any(op.startswith("collective-permute") for op in exchange)


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
