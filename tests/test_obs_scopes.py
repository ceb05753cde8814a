"""Device-side names of the BBMM phases, and host spans that record run
time only.

* The ``mode="pallas"`` training step (``value_and_grad(ExactGP.loss)``
  then Adam, the Pallas kernel in interpret mode) carries the phase scopes
  in its compiled ``op_name`` metadata: ``bbmm.mbcg`` on every op of the
  solve and its ``while`` body, ``bbmm.backward`` on the custom-VJP
  backward (its checkpointed panel stream included), ``bbmm.precond``,
  ``bbmm.logdet`` and ``optim.adam``.
* Every ``pallas_call`` is named: the kernel-matrix products
  ``kernel_matmul[_batched]``, the fused CG steps ``[panel_]fused_cg_step``
  (which a trace search for "kernel_matmul" must not find).
* A jitted ``fit_gp`` under ``obs.trace()`` records its ``fit:dispatch``
  and ``fit:sync`` host spans per step and nothing at trace time (no
  ``mbcg`` or ``engine_forward`` span); a ``jax.profiler`` capture of the
  same fit holds those host spans.
* ``PosteriorSession.query``'s latency sample ends at ready answers.
"""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import BBMMSettings
from repro.gp import BayesianLinearRegression, ExactGP, fit_gp
from repro.optim import adam
from repro.serving import PosteriorSession

jax.config.update("jax_platform_name", "cpu")

pytestmark = pytest.mark.obs

N, D = 48, 3
SETTINGS = BBMMSettings(num_probes=3, max_cg_iters=4, precond_rank=2)


def data(n=N, d=D):
    X = jax.random.uniform(jax.random.PRNGKey(0), (n, d))
    y = jnp.sin(4.0 * X[:, 0]) + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (n,))
    return X, y


def training_step(settings=SETTINGS):
    """fit_gp's step with data and targets as arguments, and its inputs."""
    model = ExactGP(kernel_type="matern52", ard=True, mode="pallas", settings=settings)
    X, y = data()
    init, update = adam(0.1)

    def step(params, opt, feed, y, key):
        loss, g = jax.value_and_grad(model.loss)(params, feed, y, key)
        params, opt = update(g, opt, params)
        return params, opt, loss

    params = model.init_params(X)
    return step, (params, init(params), model.prepare_inputs(X), y, jax.random.PRNGKey(2))


def pallas_names(jaxpr) -> list:
    """The ``name`` of every ``pallas_call`` in a jaxpr and its sub-jaxprs."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += pallas_names(inner)
    return out


@pytest.fixture(scope="module")
def op_names():
    step, args = training_step()
    text = jax.jit(step).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_mbcg_solve_and_its_loop_body_carry_the_scope(op_names):
    solve = [n for n in op_names if "jit(_mbcg_jit))/" in n]
    assert solve and all("/bbmm.mbcg/" in n for n in solve)
    assert any("/bbmm.mbcg/while/body/" in n for n in solve)
    # the Pallas kernel product runs inside the solve's loop
    assert any("/bbmm.mbcg/" in n and "/kernel_matmul/" in n for n in op_names)


def test_backward_and_its_panel_stream_carry_the_scope(op_names):
    backward = [n for n in op_names if "bbmm.backward" in n]
    assert backward
    assert any("checkpoint" in n and "dot_general" in n for n in backward)
    assert not any("checkpoint" in n and "bbmm.backward" not in n
                   for n in op_names if n.startswith("jit(step)/"))


@pytest.mark.parametrize(
    "scope", ["bbmm.precond", "bbmm.logdet", "optim.adam", "mxu.split_bf16"]
)
def test_phase_scopes_present(op_names, scope):
    assert any(scope in n for n in op_names)


def test_split_scope_holds_the_kernel_product(op_names):
    """precision="highest": every kernel-matrix product of the solve runs
    on packed bf16 splits, inside ``mxu.split_bf16``."""
    kernel = [n for n in op_names if "/kernel_matmul/" in n]
    assert kernel and all("/mxu.split_bf16/" in n for n in kernel)


def test_adam_update_is_scoped_for_both_optimizers():
    from repro.optim.adam import adamw

    params = {"w": jnp.ones(3)}
    for make in (adam, adamw):
        init, update = make(0.1)
        text = jax.jit(update).lower(params, init(params), params).compile().as_text()
        assert "optim.adam" in text


@pytest.mark.parametrize("settings, expected", [
    (SETTINGS, {"kernel_matmul"}),
    (BBMMSettings(num_probes=3, max_cg_iters=4, precond_rank=0, fuse_cg=True),
     {"fused_cg_step", "kernel_matmul"}),
], ids=["plain", "fused"])
def test_training_step_pallas_calls_are_named(settings, expected):
    step, args = training_step(settings)
    names = pallas_names(jax.make_jaxpr(step)(*args).jaxpr)
    assert names and set(names) == expected


def test_every_kernel_entry_point_is_named():
    from repro.kernels.kernel_matmul import kernel_matmul as km
    from repro.kernels.kernel_matmul.ops import panel_fused_cg_step_prescaled

    n, t = 40, 3
    Xs = jnp.ones((n, D))
    s = jnp.float32(1.0)

    def batched(M):
        return km.kernel_matmul_pallas(Xs, Xs, M, s, s, interpret=True)

    names = pallas_names(jax.make_jaxpr(batched)(jnp.ones((2, n, t))).jaxpr)
    assert names == ["kernel_matmul_batched"]

    state = [jnp.ones((n, t))] * 4 + [jnp.ones((t,))] * 3

    def panel(*state):
        return panel_fused_cg_step_prescaled(Xs, *state, s, s, panel_rows=16,
                                             interpret=True)

    names = set(pallas_names(jax.make_jaxpr(panel)(*state).jaxpr))
    assert names == {"panel_fused_cg_step"}
    # a trace search for the kernel-matrix product never finds a CG step
    assert "kernel_matmul" in km.KERNEL_MATMUL and "kernel_matmul" in km.KERNEL_MATMUL_BATCHED
    assert "kernel_matmul" not in km.FUSED_CG_STEP + km.PANEL_FUSED_CG_STEP


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """Three jitted fit_gp steps under obs.trace() and a profiler capture."""
    out = str(tmp_path_factory.mktemp("profile"))
    model = ExactGP(kernel_type="matern52", ard=True, mode="pallas", settings=SETTINGS)
    X, y = data()
    jax.profiler.start_trace(out)
    try:
        with obs.trace() as col:
            fit_gp(model, X, y, steps=3, key=jax.random.PRNGKey(3))
    finally:
        jax.profiler.stop_trace()
    return col, out


def test_jitted_fit_records_host_spans_only(traced_fit):
    col, _ = traced_fit
    assert not col.spans("mbcg") and not col.spans("engine_forward")
    assert len(col.spans("fit:dispatch")) == 3
    assert len(col.spans("fit:sync")) == 3


def test_fit_spans_land_in_a_profiler_capture(traced_fit):
    from jax.profiler import ProfileData

    _, out = traced_fit
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events]
    assert names.count("fit:dispatch") == 3
    assert names.count("fit:sync") == 3


def test_no_span_records_while_jax_traces():
    with obs.trace() as col:
        @jax.jit
        def f(x):
            with obs.span("inside"):
                return x + 1

        f(jnp.ones(2))
        with obs.span("outside"):
            pass
    assert [e["name"] for e in col.spans()] == ["outside"]


def test_query_latency_sample_ends_at_ready_answers(monkeypatch):
    """The serving_query_seconds sample is taken after the answer is ready:
    a block_until_ready that takes 50 ms on the returned answer shows in
    the sample."""
    X, y = data(32, 1)
    model = BayesianLinearRegression()
    session = PosteriorSession(model, model.init_params(X), X, y)
    Xs = jnp.linspace(-1.0, 1.0, 8)[:, None]
    session.query(Xs)
    waited = []

    def slow_ready(x):
        time.sleep(0.05)
        waited.append(x)
        return x

    monkeypatch.setattr(jax, "block_until_ready", slow_ready)
    with obs.installed() as reg:
        out = session.query(Xs)
    assert len(waited) == 1 and waited[0] is out
    _, _, total, count = reg.get_histogram("serving_query_seconds", result="ok")
    assert count == 1 and total >= 0.05
