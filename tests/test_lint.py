"""The trace-invariant lint suite (repro.analysis.lint).

Two directions, both required for the auditors to be trustworthy:

* seeded violations — a deliberately O(N*D) round body, a sampler with a
  hidden ``io_callback``, and an f64 leak must each produce EXACTLY ONE
  finding naming the offending op with real source provenance (origin
  filtering: downstream consumers of an already-flagged buffer are not
  re-reported);
* clean programs — the repo's own bodies, samplers, and segment runners must
  sweep clean, which is what the CI gate (``python -m repro.analysis.lint``)
  enforces over the full registry x oracle/deployable x compiled/reference
  matrix (mirrored here as a ``slow`` test).
"""
import dataclasses

import jax
import jax.experimental
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.lint import (
    Finding,
    LintReport,
    audit_compile_once,
    audit_dtypes,
    audit_scan_safety,
    audit_width,
    audit_width_hlo,
    main,
    run_suite,
    sweep_registry,
)
from repro.api import (
    ExecutionSpec,
    ExperimentSpec,
    FederationSpec,
    SamplerSpec,
    TaskSpec,
)
from repro.core import samplers

N = 13  # distinctive client count: prime, collides with no model dimension
D = 60


def _spec(**exec_kw):
    return ExperimentSpec(
        task=TaskSpec(
            name="logreg",
            dataset="synthetic_classification",
            dataset_kwargs={"n_clients": N, "total": 40 * N, "seed": 0},
        ),
        sampler=SamplerSpec(name="kvib", kwargs={"horizon": 4}),
        federation=FederationSpec(rounds=4, budget=4, local_steps=1, batch_size=8),
        execution=ExecutionSpec(**exec_kw),
    )


# ---------------------------------------------------------------------------
# Seeded violations: exactly one finding each, right op, real provenance
# ---------------------------------------------------------------------------


def test_seeded_ond_body_yields_exactly_one_width_finding():
    """An outer product materializing (N, D) must be flagged once, at the
    multiply that introduces it — its downstream sum consumes the flagged
    buffer and is suppressed by origin filtering."""

    def bad_body(fb, delta):
        contrib = fb[:, None] * delta[None, :]  # the O(N*D) leak
        return jnp.sum(contrib, axis=0)

    closed = jax.make_jaxpr(bad_body)(
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32),
    )
    findings = audit_width(closed, N, target="bad_body")
    assert len(findings) == 1, "\n".join(f.render() for f in findings)
    (f,) = findings
    assert f.check == "width"
    assert f.op == "mul"
    assert f.shape == f"float32[{N},{D}]"
    assert "test_lint.py" in f.provenance and "bad_body" in f.provenance


def test_width_auditor_allows_n_vectors_and_integer_buffers():
    """(N,) float vectors (probabilities, feedback) and N-sized integer/key
    material ((N, R, 2) uint32 batch keys) are legitimate — no findings."""

    def fine_body(p, key):
        fb = p * 2.0  # (N,) float: fine
        keys = jax.vmap(lambda k: jax.random.split(k, 3))(
            jax.random.split(key, N)
        )  # (N, 3, 2) uint32: fine (not float)
        return fb.sum() + keys.sum()

    closed = jax.make_jaxpr(fine_body)(
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    )
    assert audit_width(closed, N) == []


def test_width_auditor_allowlist_permits_declared_buffers():
    def body(fb, delta):
        return fb[:, None] * delta[None, :]

    closed = jax.make_jaxpr(body)(
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32),
    )
    assert audit_width(closed, N, allow=[(N, D)]) == []
    assert len(audit_width(closed, N)) == 1


def test_seeded_callback_sampler_yields_exactly_one_scan_safety_finding():
    """A sampler smuggling an io_callback into update() is rejected with one
    finding naming the callback primitive and the method."""

    @dataclasses.dataclass(frozen=True)
    class SpySampler(samplers.Sampler):
        def update(self, state, draw, feedback):
            jax.experimental.io_callback(
                lambda x: None, None, feedback, ordered=True
            )
            return dataclasses.replace(state, t=state.t + 1)

    findings = audit_scan_safety(SpySampler(n=N, budget=4))
    assert len(findings) == 1, "\n".join(f.render() for f in findings)
    (f,) = findings
    assert f.check == "scan_safety"
    assert f.op == "io_callback"
    assert f.target.endswith(".update")
    assert "test_lint.py" in f.provenance


def test_seeded_f64_leak_yields_exactly_one_dtype_finding():
    """An astype(float64) leak is flagged once, at the convert that
    introduces the wide dtype — the arithmetic consuming it is suppressed."""

    def leaky(x):
        y = x.astype(jnp.float64)
        return (y * 2.0).sum()

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(leaky)(jax.ShapeDtypeStruct((N,), jnp.float32))
    findings = audit_dtypes(closed, target="leaky")
    assert len(findings) == 1, "\n".join(f.render() for f in findings)
    (f,) = findings
    assert f.check == "dtype"
    assert f.op == "convert_element_type"
    assert f.shape == f"float64[{N}]"
    assert "test_lint.py" in f.provenance and "leaky" in f.provenance


def test_data_dependent_control_flow_surfaces_as_finding():
    @dataclasses.dataclass(frozen=True)
    class BranchySampler(samplers.Sampler):
        def probabilities(self, state):
            if state.stats[0] > 0:  # tracer bool conversion at trace time
                return jnp.full((self.n,), 0.5)
            return jnp.full((self.n,), self.budget / self.n)

    findings = audit_scan_safety(BranchySampler(n=N, budget=4))
    assert len(findings) == 1
    (f,) = findings
    assert f.check == "scan_safety" and f.target.endswith(".probabilities")
    assert "control flow" in f.message


def test_update_aval_drift_surfaces_as_finding():
    """update() silently retyping a state leaf breaks the scan carry on the
    next round; the checker reports it at the sampler, statically."""

    @dataclasses.dataclass(frozen=True)
    class DriftySampler(samplers.Sampler):
        def update(self, state, draw, feedback):
            return dataclasses.replace(
                state, t=(state.t + 1).astype(jnp.float32)
            )

    findings = audit_scan_safety(DriftySampler(n=N, budget=4))
    assert len(findings) == 1
    assert "drifts state leaf" in findings[0].message


def test_bad_probabilities_shape_surfaces_as_finding():
    @dataclasses.dataclass(frozen=True)
    class WideProbs(samplers.Sampler):
        def probabilities(self, state):
            return jnp.full((self.n, 2), 0.5)

    findings = audit_scan_safety(WideProbs(n=N, budget=4))
    assert len(findings) == 1
    assert "probabilities must return" in findings[0].message


# ---------------------------------------------------------------------------
# HLO-level width audit
# ---------------------------------------------------------------------------


def test_hlo_width_audit_flags_compiled_leak_and_passes_clean_body():
    def bad(fb, delta):
        return (fb[:, None] * delta[None, :]).sum(axis=0)

    def fine(fb, delta):
        return fb.sum() * delta

    args = (
        jax.ShapeDtypeStruct((N,), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32),
    )
    bad_text = jax.jit(bad).lower(*args).compile().as_text()
    fine_text = jax.jit(fine).lower(*args).compile().as_text()
    bad_findings = audit_width_hlo(bad_text, N, target="bad")
    assert bad_findings, "compiled O(N*D) buffer must be visible in HLO"
    assert all(f.check == "width" for f in bad_findings)
    assert audit_width_hlo(fine_text, N, target="fine") == []


# ---------------------------------------------------------------------------
# Compile-once guard
# ---------------------------------------------------------------------------


def _toy_segment(params0, rounds=6):
    from repro.fed.state import TrainState, init_metric_buffers, make_segment_fn

    def body(carry, xs):
        p, s = carry
        return (p + 1.0, s), {"loss": jnp.sum(p)}

    def derive(k, _):
        k2, kd = jax.random.split(k)
        return k2, jnp.stack([kd, kd])

    seg = make_segment_fn(body, derive, with_opt_state=False, with_round_index=False)
    key = jax.random.PRNGKey(0)
    s0 = jnp.zeros((3,), jnp.float32)
    state = TrainState(
        params=params0,
        opt_state=(),
        sampler=s0,
        metrics=init_metric_buffers(
            body, (params0, s0), jnp.stack([key, key]), rounds
        ),
        round=jnp.zeros((), jnp.int32),
        key=key,
    )
    return seg, state


def test_compile_once_clean_on_strong_typed_carry():
    seg, state = _toy_segment(jnp.zeros((4,), jnp.float32))
    assert audit_compile_once(seg, state, 2) == []


def test_compile_once_flags_weak_typed_carry_on_resume():
    """A weak-typed carry leaf survives segment boundaries but not the numpy
    round trip a checkpoint applies — the guard must catch the resume
    recompile that causes."""
    params0 = jnp.asarray(1.0)  # python-scalar conversion: weak_type=True
    assert params0.weak_type
    seg, state = _toy_segment(params0)
    findings = audit_compile_once(seg, state, 2)
    assert len(findings) == 1
    assert findings[0].check == "compile_once"
    assert "resume recompiles" in findings[0].message


def test_compile_once_flags_declared_donation_mismatch():
    seg, state = _toy_segment(jnp.zeros((4,), jnp.float32))
    tampered = dict(seg._lint)
    tampered["donate_argnums"] = (0,) if not tampered["donate_argnums"] else ()
    seg._lint = tampered
    findings = audit_compile_once(seg, state, 2, resume=False)
    assert any("donation mismatch" in f.message for f in findings)


def test_compile_once_clean_on_real_segment_runner():
    """The actual fed.server segmented runner: one compile across segments
    and across the checkpoint-transport round trip."""
    from repro.data import synthetic_classification
    from repro.fed import FedConfig, logistic_regression
    from repro.fed.server import build_segment_runner

    ds = synthetic_classification(n_clients=N, total=40 * N, seed=0)
    cfg = FedConfig(rounds=6, budget=4, local_steps=1, batch_size=8,
                    oracle_metrics=False)
    sampler = samplers.make_sampler("kvib", n=N, budget=4, horizon=6)
    segment, state = build_segment_runner(
        logistic_regression(), ds, sampler, cfg, None
    )
    assert audit_compile_once(segment, state, 2, target="segment") == []


# ---------------------------------------------------------------------------
# Registry-wide scan-safety + the suite front door
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", samplers.sampler_names())
def test_registered_samplers_are_scan_safe(name):
    s = samplers.make_sampler(name, n=N, budget=4)
    findings = audit_scan_safety(s, target=f"sampler:{name}")
    assert findings == [], "\n".join(f.render() for f in findings)


def test_run_suite_clean_on_deployable_compiled_spec():
    """The front door on a real spec: all five passes (scan-safety, dtype,
    jaxpr width, compile-once, HLO width) run and come back clean."""
    report = run_suite(_spec(compiled=True, oracle_metrics=False))
    assert report.ok, report.render()
    kinds = {c.split(":", 1)[0] for c in report.checked}
    assert kinds == {"scan_safety", "dtype", "width", "compile_once", "width_hlo"}


def test_run_suite_skips_width_on_oracle_and_scatter_bodies():
    rep_oracle = run_suite(_spec(compiled=False, oracle_metrics=True))
    assert rep_oracle.ok, rep_oracle.render()
    assert not any(c.startswith("width") for c in rep_oracle.checked)
    rep_scatter = run_suite(
        _spec(compiled=False, oracle_metrics=False, exact_oracle_equiv=True)
    )
    assert rep_scatter.ok, rep_scatter.render()
    assert not any(c.startswith("width") for c in rep_scatter.checked)


def test_api_lint_wrapper_forwards_to_run_suite():
    import repro.api as api

    report = api.lint(_spec(compiled=False), hlo=False, compile_guard=False)
    assert isinstance(report, LintReport)
    assert report.ok, report.render()


def test_report_render_and_ok():
    rep = LintReport()
    rep.add([], "width:x")
    assert rep.ok and "clean" in rep.render()
    rep.add(
        [Finding(check="width", target="t", message="boom", op="mul",
                 shape="float32[13,60]")],
        "width:y",
    )
    assert not rep.ok
    text = rep.render()
    assert "1 finding" in text and "mul" in text and "boom" in text


def test_cli_single_sampler_fast_sweep_exit_codes(tmp_path, capsys):
    """main() is the ``python -m repro.analysis.lint`` entry point: 0 on a
    clean sweep/spec, nonzero would mean a finding."""
    rc = main(["--samplers", "uniform_isp", "--fast", "--quiet"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "lint clean" in out

    path = tmp_path / "spec.json"
    _spec(compiled=False).save(path)
    assert main(["--spec", str(path)]) == 0


def test_hlo_unknown_dtype_is_a_named_error():
    """analysis.hlo used to KeyError on unknown dtype tokens deep inside
    byte accounting; now it's a catchable, self-describing error."""
    from repro.analysis.hlo import DTYPE_BYTES, UnknownDtypeError, dtype_bytes

    assert dtype_bytes("f32") == 4
    with pytest.raises(UnknownDtypeError) as ei:
        dtype_bytes("f4e2m1")
    assert ei.value.dtype == "f4e2m1"
    assert "DTYPE_BYTES" in str(ei.value)
    assert isinstance(ei.value, KeyError)  # backward-compatible except clauses
    assert set(DTYPE_BYTES) >= {"f32", "bf16", "s32", "pred"}


@pytest.mark.slow  # the CI gate: full registry x fidelity x mode, with compiles
def test_full_registry_sweep_is_clean():
    report = sweep_registry()
    assert report.ok, report.render()
    # 9 samplers x 2 fidelities x 2 modes, every cell at least scan-safety +
    # dtype checked
    assert len(report.checked) >= 9 * 2 * 2 * 2
