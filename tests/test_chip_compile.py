"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a ``v5e:2x2`` topology that is described, not present.  Each test
compiles at the real size of the Protein configuration (n=45 730, d=9
lane-padded to 128, t=11 probes+y lane-padded to 128), or of the Elevators
one for the packed ``precision="highest"`` launch (n=16 599, d=18), with
``interpret=False`` and asserts that the Mosaic kernel
(``tpu_custom_call``) is in the compiled program.  This catches what the
interpret-mode tests cannot: unaligned slices, VMEM overruns, programs
that do not fit the device, and gradients that cannot lower.

The topology is described only inside the module-scoped ``topo`` fixture,
never while a module is imported: every xdist worker then collects the
same tests, and only the worker given this file loads the TPU library.
The persistent compile cache is off around these compiles (an entry
compiled for an absent chip cannot be read back).
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

N, D, T = 45_730, 9, 11  # Protein-shaped exact GP, 10 probes + y
LANES = 128
N4, D4 = 430_080, 3  # 3DRoad-shaped, divisible over the 4-device mesh
TPU_KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return sds


@pytest.fixture(scope="module")
def mesh4(topo):
    from repro.launch.mesh import make_mesh

    return make_mesh((4,), ("data",), devices=topo.devices[:4])


def _compile_text(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert TPU_KERNEL in text
    return text


@pytest.mark.parametrize(
    "compute_dtype,batch",
    [("float32", None), ("bfloat16", None), ("float32", 2)],
    ids=["f32", "bf16", "f32-batched"],
)
def test_kernel_matmul_compiles(one_chip, compute_dtype, batch):
    from repro.kernels.kernel_matmul.kernel_matmul import kernel_matmul_pallas

    dtype = jnp.dtype(compute_dtype)
    Xs = one_chip((N, LANES), dtype)
    M = one_chip((N, LANES) if batch is None else (batch, N, LANES), dtype)

    def f(Xs, M, outputscale, sigma2):
        return kernel_matmul_pallas(
            Xs, Xs, M, outputscale, sigma2, kernel_type="matern52",
            interpret=False, compute_dtype=compute_dtype,
        )

    _compile_text(f, Xs, M, one_chip(()), one_chip(()))


def _kernel_dots(jaxpr):
    """dtypes of the MXU operands of every dot in every Pallas kernel body."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(tuple(v.aval.dtype for v in eqn.invars))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _kernel_dots(inner)
    return out


@pytest.mark.parametrize(
    "batch,t",
    [(None, T), (2, T), (None, 64)],
    ids=["elevators", "elevators-batched", "wide-rhs"],
)
def test_split_kernel_matmul_compiles(one_chip, batch, t):
    """The ``precision="highest"`` launch on packed bf16 splits, at the
    Elevators size (n=16 599, d=18: six groups in 128 lanes; t=11: three in
    128), with the packing and the lane-group sum around it: the Mosaic
    kernel is there and its MXU operands are bf16 — all of them where the
    product is packed, the distances' where t=64 keeps the f32 HIGHEST
    product."""
    from repro.kernels.kernel_matmul.ops import fused_kernel_matmul_prescaled

    n, d = 16_599, 18
    Xs = one_chip((n, d))
    M = one_chip((n, t) if batch is None else (batch, n, t))

    def f(Xs, M, outputscale, sigma2):
        return fused_kernel_matmul_prescaled(
            Xs, Xs, M, outputscale, sigma2, kernel_type="matern52", interpret=False
        )

    args = (Xs, M, one_chip(()), one_chip(()))
    _compile_text(f, *args)
    dots = _kernel_dots(jax.make_jaxpr(f)(*args).jaxpr)
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    want = [(bf16, bf16)] * 4 if t <= 42 else [(bf16, bf16), (f32, f32)]
    assert dots == want, dots


def _cg_state(sds, n):
    return [sds((n, T)) for _ in range(4)] + [sds((T,)) for _ in range(3)]


def test_fused_cg_step_compiles(one_chip):
    from repro.kernels.kernel_matmul.ops import fused_cg_step_prescaled

    def f(Xs, U, R, D_, V, al, be, ga, outputscale, sigma2):
        return fused_cg_step_prescaled(
            Xs, U, R, D_, V, al, be, ga, outputscale, sigma2,
            kernel_type="matern52", interpret=False,
        )

    _compile_text(
        f, one_chip((N, LANES)), *_cg_state(one_chip, N), one_chip(()), one_chip(())
    )


def test_panel_fused_step_with_row_offset_compiles(one_chip):
    """The partitioned fused step: one launch per row-panel, each at its
    own non-zero ``row_offset``, plus a ragged last panel."""
    from repro.kernels.kernel_matmul.ops import (
        choose_panel_rows,
        panel_fused_cg_step_prescaled,
    )

    p = choose_panel_rows(N, rhs_cols=T, fused=True)
    assert 0 < p < N and N % p, p  # several panels, ragged tail

    def f(Xs, U, R, D_, V, al, be, ga, outputscale, sigma2):
        return panel_fused_cg_step_prescaled(
            Xs, U, R, D_, V, al, be, ga, outputscale, sigma2,
            panel_rows=p, kernel_type="matern52", interpret=False,
        )

    text = _compile_text(
        f, one_chip((N, LANES)), *_cg_state(one_chip, N), one_chip(()), one_chip(())
    )
    assert text.count(TPU_KERNEL) >= 2  # scanned panels + the ragged tail


def test_pallas_sharded_matmul_compiles_over_four_chips(mesh4):
    from repro.kernels.kernel_matmul.ops import sharded_kernel_matmul_prescaled

    rep = NamedSharding(mesh4, P())
    rows = NamedSharding(mesh4, P("data", None))
    Xs = jax.ShapeDtypeStruct((N4, LANES), jnp.float32, sharding=rep)
    M = jax.ShapeDtypeStruct((N4, T), jnp.float32, sharding=rows)
    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)

    def f(Xs, M, outputscale):
        return sharded_kernel_matmul_prescaled(
            Xs, M, outputscale, mesh4, kernel_type="matern52", interpret=False
        )

    compiled = jax.jit(f).lower(Xs, M, s).compile()
    assert TPU_KERNEL in compiled.as_text()
    assert "all-gather" in compiled.as_text()
    out = compiled.output_shardings
    assert out.is_equivalent_to(rows, 2) and len(out.device_set) == 4, out


def test_pallas_mll_gradient_compiles(one_chip, monkeypatch):
    """``mode="pallas"`` MLL gradient at the real size: the forward CG loop
    launches the kernel, the custom VJP differentiates the XLA panel
    stream — ``jax.grad`` never meets a ``pallas_call``."""
    import repro.kernels.kernel_matmul.ops as ops
    from repro.core import BBMMSettings
    from repro.gp import ExactGP

    # the described chip is not the default backend: steer the platform
    # probe so the kernel is built for Mosaic, not the interpreter
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    gp = ExactGP(
        kernel_type="matern52", mode="pallas", ard=True,
        settings=BBMMSettings(num_probes=T - 1, precond_rank=5),
    )
    params = {
        "raw_lengthscale": one_chip((D,)),
        "raw_outputscale": one_chip(()),
        "raw_noise": one_chip(()),
    }
    key = one_chip((2,), jnp.uint32)
    compiled = jax.jit(jax.value_and_grad(gp.loss)).lower(
        params, one_chip((N, D)), one_chip((N,)), key
    ).compile()
    assert TPU_KERNEL in compiled.as_text()
    mem = compiled.memory_analysis()
    # the backward streams panels: nowhere near a dense 8.4 GB K
    assert mem.temp_size_in_bytes < 2 * 1024**3, mem
