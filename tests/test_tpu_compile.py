"""Compile the main path's kernels and the jitted sweep for a TPU v5e chip
that is described, not attached, at real widths.

Interpret mode on the CPU cannot show what Mosaic refuses (a dynamic index
into a loaded value, a block that breaks the (8, 128) tiling, an op with no
lowering), so each kernel is compiled with ``interpret=False`` and must hold
a ``tpu_custom_call``. Nothing runs: a pass says the chip's compiler accepts
the program, not that it is fast or right (tests/test_kernels.py checks
results in interpret mode).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import functools
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from bench import xtrace
from benchmarks.jaxsim_bench import candidate_placements
from repro.core import jaxsim
from repro.core import simulator as S
from repro.core.simulator import _spec_graph
from repro.kernels.cold_scan import cold_scan, kernel_lanes
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip; keep it out of the cache
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_cold_scan_compiles(one_chip, dtype):
    """One sweep row at the throughput shape (2^20 requests), in both of the
    sweep's dtypes: the gaps are compared in the input dtype, the kernel
    itself only selects bits."""
    n = 2**20
    with jax.enable_x64(dtype == jnp.float64):
        compile_for_chip(
            lambda t0, w, c: cold_scan(t0, w, c, 900.0, interpret=False),
            spec(one_chip, (n,), dtype),
            spec(one_chip, (1, n), dtype),
            spec(one_chip, (1, n), dtype),
        )


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    """qwen3-1.7b: 16 query heads, 8 kv heads, head_dim 128, T = 2048."""
    q = spec(one_chip, (1, 2048, 16, 128), jnp.bfloat16)
    kv = spec(one_chip, (1, 2048, 8, 128), jnp.bfloat16)
    compile_for_chip(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), q, kv, kv
    )


def test_rglru_scan_compiles_at_recurrentgemma_width(one_chip):
    x = spec(one_chip, (1, 2048, 4096), jnp.float32)
    compile_for_chip(
        lambda a, b: rglru_scan(a, b, chunk=256, block_w=512, interpret=False), x, x
    )


def test_ssd_scan_compiles_at_mamba2_width(one_chip):
    """mamba2-370m: 32 heads of 64, state 128, chunk 256."""
    L, H, P, N = 2048, 32, 64, 128
    compile_for_chip(
        lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, 256, interpret=False),
        spec(one_chip, (1, L, H, P), jnp.float32),
        spec(one_chip, (1, L, H), jnp.float32),
        spec(one_chip, (H,), jnp.float32),
        spec(one_chip, (1, L, N), jnp.float32),
        spec(one_chip, (1, L, N), jnp.float32),
    )


def test_rmsnorm_compiles(one_chip):
    compile_for_chip(
        lambda x, w: rmsnorm(x, w, interpret=False),
        spec(one_chip, (4096, 2048), jnp.bfloat16),
        spec(one_chip, (2048,), jnp.float32),
    )


def compile_sweep(sharding, monkeypatch, n_seeds, n_placements, n, dtype,
                  placements=None, edges=None):
    """The whole ``_sweep`` with the kernel path forced and compiled: on the
    CPU the kernel would otherwise pick interpret mode while tracing, and
    the program would hold no kernel. Forced through a jit named
    ``cold_scan``, as ``ops.cold_scan`` is: the kernel's instruction takes
    the name, and the device trace's reader finds the kernel by it. The
    workflow is the Fig-4 chain's candidates unless ``placements`` (and a
    DAG's ``edges``) are given."""
    forced = functools.partial(cold_scan, interpret=False)
    monkeypatch.setattr(
        jaxsim, "cold_scan_kernel", jax.jit(functools.wraps(cold_scan)(forced))
    )
    if placements is None:
        placements = candidate_placements(n_placements)
    sim = S.WorkflowSimulator(S.paper_platforms(), seed=0)
    order, _, preds, succs = _spec_graph(placements[0], edges)
    if edges is None:
        step_sets = [dict(enumerate(p)) for p in placements]
    else:
        step_sets = [{s.name: s for s in p} for p in placements]
    with jax.enable_x64(True):
        placed, sigmas, graph, _ = jaxsim._build(
            sim, order, step_sets, preds, succs, np.arange(n) * 1.0, None, dtype
        )

        def shaped(a):
            a = np.asarray(a)
            return spec(sharding, a.shape, a.dtype)

        args = (
            spec(sharding, (n_seeds, 2), np.uint32),
            jax.tree_util.tree_map(shaped, placed),
            jax.tree_util.tree_map(shaped, sigmas),
            jax.tree_util.tree_map(shaped, graph),
            spec(sharding, (n,), dtype),
            spec(sharding, (), dtype),
            spec(sharding, (), dtype),
        )
        return jaxsim._sweep.lower(
            *args, None, prefetch=True, use_drift=False, use_pallas=True,
            use_stream=False, use_faults=False,
        ).compile()


def kernel_calls(compiled):
    """The program's ``cold_scan`` kernel calls, as the (requests, lanes)
    shape of each one's code plane."""
    kernels = [
        line for line in compiled.as_text().splitlines() if xtrace.is_kernel(line)
    ]
    assert all(
        xtrace.base_name(xtrace.instr_name(k)) == "cold_scan" for k in kernels
    )
    planes = [
        re.findall(r"operand_layout_constraints=\{s32\[([0-9,]*)\]", k)
        for k in kernels
    ]
    assert all(len(p) == 1 for p in planes)
    return [tuple(int(d) for d in p[0].split(",")) for p in planes]


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_sweep_compiles_with_the_pallas_cold_scan(one_chip, monkeypatch, dtype):
    """The whole ``_sweep`` at the scorer's shape (8 seeds x 32 placements x
    512 requests), in both of the sweep's dtypes."""
    compiled = compile_sweep(one_chip, monkeypatch, 8, 32, 512, dtype)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "n_seeds,n_placements,n", [(8, 32, 512), (2, 4, 2**20)], ids=["decide", "throughput"]
)
def test_sweep_folds_its_rows_into_one_cold_scan_call(
    one_chip, monkeypatch, n_seeds, n_placements, n
):
    """At the benchmark's two sweep shapes the (seed, placement) rows fold
    into the kernel's lanes: the program holds one kernel, named
    ``cold_scan`` as the device trace's reader finds it, over one 2-D
    (requests, lanes) plane; not a tile of 128 lanes per row, which took
    8.9 GiB of temporaries at the throughput shape."""
    compiled = compile_sweep(one_chip, monkeypatch, n_seeds, n_placements, n,
                             np.float32)
    kernels = [
        line for line in compiled.as_text().splitlines() if xtrace.is_kernel(line)
    ]
    assert len(kernels) == 1
    assert xtrace.base_name(xtrace.instr_name(kernels[0])) == "cold_scan"
    (operand,) = re.findall(r"operand_layout_constraints=\{s32\[([0-9,]*)\]",
                            kernels[0])
    rows = n_seeds * n_placements
    lanes = 128 * -(-rows // 128)
    assert kernel_lanes(rows) == lanes
    assert tuple(int(d) for d in operand.split(",")) == (-(-n // 256) * 256, lanes)
    if n == 2**20:
        assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30


def test_genome_sweep_makes_one_cold_scan_call_per_level(one_chip, monkeypatch):
    """The 1000Genome workflow of one chromosome (64 steps, 3 levels) at
    the replay mix's 2 seeds x 4 placements: three kernel calls, the 49
    sources' 392 rows in 512 lanes, the merge's 8 rows and the 14 sinks'
    112 rows in 128 each; 768 lanes, where one call per node took 64 x
    128. The request count is cut to keep the compile short: it sets each
    plane's length, not its lanes."""
    from repro.dag import genome_dag_1chr

    steps, edges = genome_dag_1chr()
    plats = [p.name for p in S.paper_platforms()]
    cands = [
        [replace(s, platform=p) if s.name[:12] == "individuals_"
         and s.name != "individuals_merge" else s for s in steps]
        for p in plats
    ]
    n = 2048
    compiled = compile_sweep(one_chip, monkeypatch, 2, 4, n, np.float32,
                             placements=cands, edges=edges)
    calls = kernel_calls(compiled)
    assert sorted(calls) == [(n, 128), (n, 128), (n, 512)]
    assert sum(lanes for _, lanes in calls) == 768


def test_a_deep_chain_scans_its_levels_through_one_cold_scan_call(
    one_chip, monkeypatch
):
    """A chain's levels are one run of one width: the program scans them
    through one kernel instruction, as deep as the chain is, so a 64-step
    chain compiles to the 4-step chain's one call and not to 64."""
    chain = [
        S.SimStep(f"step_{i:02d}", "gcf", compute=S.Dist(0.5, 0.2),
                  fetch=S.Dist(0.1, 0.3))
        for i in range(64)
    ]
    n = 2048
    compiled = compile_sweep(one_chip, monkeypatch, 2, 4, n, np.float32,
                             placements=[chain] * 4)
    assert kernel_calls(compiled) == [(n, 128)]
