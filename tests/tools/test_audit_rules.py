"""Per-rule fixture coverage for the d9d-audit compiled-artifact
checker (tools/audit/, docs/design/static_analysis.md).

Two layers, mirroring how the checker is built:

- **rule units** over synthetic fact dicts: one true-positive and one
  true-negative per rule (D9D100–D9D104), the manifest's
  new/baselined/stale diff semantics, the mandatory-reason policy, and
  fingerprint stability;
- **real-artifact fixtures**: tiny programs compiled through
  ``tracked_jit`` with capture on — a deliberately un-donatable buffer,
  a baked-constant closure, a collective-bearing fake serve step, a
  host-callback program — asserting the facts extracted from the
  actual jaxpr/HLO drive the same rules, plus the opt-in and
  compile-time-only contracts of the capture layer itself.
"""

import json

import numpy as np
import pytest

from tools.audit import manifest as manifest_mod
from tools.audit.rules import Violation, run_rules


def fact(**over) -> dict:
    base = {
        "name": "x/step",
        "context": "ctx",
        "collectives": {},
        "num_partitions": 1,
        "donated_declared": 0,
        "donated_bytes": 0,
        "aliased_pairs": 0,
        "consts": [],
        "const_bytes_total": 0,
        "n_consts": 0,
        "dtype_ops": {},
        "f64_ops": [],
        "f32_matmuls": 0,
        "callbacks": [],
    }
    base.update(over)
    return base


def manifest(expectations=None, baseline=None, defaults=None) -> dict:
    return {
        "version": 1,
        "defaults": defaults or {},
        "expectations": expectations or {},
        "baseline": baseline or [],
    }


def rules_of(report):
    return [v.rule for v in report.violations]


# -- D9D100 collective census --------------------------------------------


class TestCollectiveCensus:
    def test_no_collectives_contract_fires_on_any_collective(self):
        exp = {"ctx": {"x/step": {"no_collectives": True}}}
        report = run_rules(
            [fact(collectives={"all-gather": 1})], manifest(exp)
        )
        assert rules_of(report) == ["D9D100"]
        assert "all-gather" in report.violations[0].message

    def test_no_collectives_contract_clean(self):
        exp = {"ctx": {"x/step": {"no_collectives": True}}}
        report = run_rules([fact()], manifest(exp))
        assert report.violations == []

    def test_exact_census_mismatch_fires(self):
        exp = {"ctx": {"x/step": {
            "collectives": {"all-reduce": 6, "all-gather": 9},
        }}}
        report = run_rules(
            [fact(collectives={"all-reduce": 6, "all-gather": 8})],
            manifest(exp),
        )
        assert rules_of(report) == ["D9D100"]

    def test_exact_census_match_clean(self):
        exp = {"ctx": {"x/step": {
            "collectives": {"all-reduce": 6, "all-gather": 9},
        }}}
        report = run_rules(
            [fact(collectives={"all-reduce": 6, "all-gather": 9})],
            manifest(exp),
        )
        assert report.violations == []

    def test_glob_pattern_matches(self):
        exp = {"ctx": {"serve/fused_k*": {"no_collectives": True}}}
        report = run_rules(
            [fact(name="serve/fused_k4", collectives={"all-reduce": 1})],
            manifest(exp),
        )
        assert rules_of(report) == ["D9D100"]
        assert report.unmatched_expectations == []

    def test_census_checks_last_signature_only(self):
        """A warmup variant's census is not the contract: the last
        compiled signature is the program the loop keeps dispatching
        (the PipelinedOptimizer first-step case)."""
        exp = {"ctx": {"x/step": {
            "collectives": {"all-gather": 2},
        }}}
        warmup = fact(collectives={"all-gather": 1})
        steady = fact(collectives={"all-gather": 2})
        assert run_rules([warmup, steady], manifest(exp)).violations == []
        # and the reversed order DOES fire — order is meaningful
        assert rules_of(
            run_rules([steady, warmup], manifest(exp))
        ) == ["D9D100"]

    def test_unmatched_expectation_reported(self):
        """A contract whose executable was renamed (or whose leg was
        dropped) must not silently stop being checked."""
        exp = {"ctx": {"x/renamed_step": {"no_collectives": True}}}
        report = run_rules([fact()], manifest(exp))
        assert report.unmatched_expectations == [("ctx", "x/renamed_step")]
        # contexts with no facts at all are notes, not failures
        exp2 = {"other_ctx": {"y": {"no_collectives": True}}}
        report2 = run_rules([fact()], manifest(exp2))
        assert report2.unmatched_expectations == []
        assert report2.unchecked_contexts == ["other_ctx"]

    def test_no_expectation_means_unchecked(self):
        report = run_rules(
            [fact(collectives={"all-reduce": 3})], manifest()
        )
        assert report.violations == []


# -- D9D101 donation coverage --------------------------------------------


class TestDonationCoverage:
    def test_dropped_donation_fires(self):
        report = run_rules(
            [fact(donated_declared=3, donated_bytes=1024, aliased_pairs=2)],
            manifest(),
        )
        assert rules_of(report) == ["D9D101"]
        assert "double-buffered" in report.violations[0].message

    def test_full_coverage_clean(self):
        report = run_rules(
            [fact(donated_declared=3, aliased_pairs=3)], manifest()
        )
        assert report.violations == []

    def test_undonated_executable_clean(self):
        report = run_rules([fact()], manifest())
        assert report.violations == []


# -- D9D102 baked constants ----------------------------------------------


class TestBakedConstants:
    def test_large_const_fires(self):
        c = {"bytes": 400_000, "shape": [100, 1000], "dtype": "float32"}
        report = run_rules(
            [fact(consts=[c], const_bytes_total=400_000, n_consts=1)],
            manifest(),
        )
        assert rules_of(report) == ["D9D102"]
        assert "install_weights" in report.violations[0].message

    def test_small_const_clean(self):
        c = {"bytes": 64, "shape": [16], "dtype": "float32"}
        report = run_rules(
            [fact(consts=[c], const_bytes_total=64, n_consts=1)],
            manifest(),
        )
        assert report.violations == []

    def test_per_executable_threshold_override(self):
        c = {"bytes": 4096, "shape": [1024], "dtype": "float32"}
        exp = {"ctx": {"x/step": {"max_const_bytes": 1024}}}
        report = run_rules([fact(consts=[c])], manifest(exp))
        assert rules_of(report) == ["D9D102"]
        # default threshold would have let it through
        assert run_rules([fact(consts=[c])], manifest()).violations == []

    def test_defaults_threshold_from_manifest(self):
        c = {"bytes": 4096, "shape": [1024], "dtype": "float32"}
        report = run_rules(
            [fact(consts=[c])],
            manifest(defaults={"max_const_bytes": 100}),
        )
        assert rules_of(report) == ["D9D102"]


# -- D9D103 dtype discipline ---------------------------------------------


class TestDtypeDiscipline:
    def test_f64_always_fires(self):
        report = run_rules([fact(f64_ops=["add", "mul"])], manifest())
        assert rules_of(report) == ["D9D103"]
        assert "x64" in report.violations[0].message

    def test_f32_matmuls_fire_only_under_bf16_policy(self):
        f = fact(f32_matmuls=5)
        assert run_rules([f], manifest()).violations == []
        exp = {"ctx": {"x/step": {"dtype_policy": "bf16_compute"}}}
        report = run_rules([f], manifest(exp))
        assert rules_of(report) == ["D9D103"]

    def test_bf16_program_clean_under_policy(self):
        exp = {"ctx": {"x/step": {"dtype_policy": "bf16_compute"}}}
        report = run_rules(
            [fact(dtype_ops={"bfloat16": 40, "float32": 6})],
            manifest(exp),
        )
        assert report.violations == []

    def test_require_dtypes_fires_when_census_widens(self):
        # the low-precision serving contract: a quantized program whose
        # census lost int8 silently resurrected wide pools
        exp = {"ctx": {"x/step": {"require_dtypes": ["int8", "float32"]}}}
        report = run_rules(
            [fact(dtype_ops={"float32": 40, "int32": 3})], manifest(exp)
        )
        assert rules_of(report) == ["D9D103"]
        v = report.violations[0]
        assert "int8" in v.message and v.key == "require_dtypes:int8"

    def test_require_dtypes_clean_when_present(self):
        exp = {"ctx": {"x/step": {"require_dtypes": ["int8", "float32"]}}}
        report = run_rules(
            [fact(dtype_ops={"int8": 4, "float32": 40, "int32": 3})],
            manifest(exp),
        )
        assert report.violations == []
        # and without an expectation the census is unconstrained
        assert run_rules(
            [fact(dtype_ops={"float32": 40})], manifest()
        ).violations == []


# -- D9D104 host callbacks -----------------------------------------------


class TestHostCallbacks:
    def test_callback_fires(self):
        report = run_rules(
            [fact(callbacks=["pure_callback"])], manifest()
        )
        assert rules_of(report) == ["D9D104"]

    def test_no_callback_clean(self):
        assert run_rules([fact()], manifest()).violations == []


# -- manifest / baseline semantics ---------------------------------------


class TestManifestSemantics:
    def _violation(self, key="k") -> Violation:
        return Violation(
            rule="D9D101", context="ctx", executable="x/step",
            message="m", key=key,
        )

    def test_fingerprint_stable_and_key_sensitive(self):
        a, b = self._violation(), self._violation()
        assert a.fingerprint() == b.fingerprint()
        assert a.fingerprint() != self._violation("other").fingerprint()

    def test_diff_new_baselined_stale(self):
        v = self._violation()
        m = manifest(baseline=[{
            "fingerprint": v.fingerprint(), "rule": v.rule,
            "reason": "accepted for a reason",
        }])
        diff = manifest_mod.diff_against_baseline([v], m)
        assert diff.ok and diff.baselined == [v] and diff.stale == []
        # a baselined entry that stopped firing is stale
        diff2 = manifest_mod.diff_against_baseline([], m)
        assert diff2.ok and diff2.stale == m["baseline"]
        # an unknown violation is new
        diff3 = manifest_mod.diff_against_baseline(
            [self._violation("fresh")], m
        )
        assert not diff3.ok and len(diff3.new) == 1

    def test_load_rejects_reasonless_baseline(self, tmp_path):
        p = tmp_path / "AUDIT_BASELINE.json"
        p.write_text(json.dumps({
            "version": 1, "expectations": {},
            "baseline": [{"fingerprint": "abc", "rule": "D9D101"}],
        }))
        with pytest.raises(manifest_mod.AuditManifestError):
            manifest_mod.load(p)
        p.write_text(json.dumps({
            "version": 1, "expectations": {},
            "baseline": [{
                "fingerprint": "abc", "rule": "D9D101",
                "reason": manifest_mod.FILL_ME,
            }],
        }))
        with pytest.raises(manifest_mod.AuditManifestError):
            manifest_mod.load(p)

    def test_load_rejects_non_manifest(self, tmp_path):
        p = tmp_path / "AUDIT_BASELINE.json"
        p.write_text("{\"metrics\": {}}")
        with pytest.raises(manifest_mod.AuditManifestError):
            manifest_mod.load(p)
        p.write_text("not json")
        with pytest.raises(manifest_mod.AuditManifestError):
            manifest_mod.load(p)

    def test_write_baseline_carries_reasons_and_marks_new(self, tmp_path):
        p = tmp_path / "AUDIT_BASELINE.json"
        v_old, v_new = self._violation("old"), self._violation("new")
        p.write_text(json.dumps({
            "version": 1,
            "expectations": {"ctx": {"x/step": {"no_collectives": True}}},
            "baseline": [{
                "fingerprint": v_old.fingerprint(), "rule": v_old.rule,
                "reason": "the old reason",
            }],
        }))
        data = manifest_mod.write_baseline(p, [v_old, v_new])
        by_fp = {e["fingerprint"]: e for e in data["baseline"]}
        assert by_fp[v_old.fingerprint()]["reason"] == "the old reason"
        assert by_fp[v_new.fingerprint()]["reason"].startswith("FILL-ME")
        # expectations survive the rewrite, and the FILL-ME entry keeps
        # the file un-loadable until a human writes the reason
        assert json.loads(p.read_text())["expectations"]
        with pytest.raises(manifest_mod.AuditManifestError):
            manifest_mod.load(p)


# -- real-artifact fixtures (capture on actual compiles) -----------------


@pytest.fixture
def capture():
    from d9d_tpu.telemetry import audit_capture, introspect

    audit_capture.enable(True)
    mark = len(introspect.inventory())
    yield introspect, mark
    audit_capture.enable(None)


def _facts_since(introspect, mark):
    return [
        r.audit
        for r in introspect.inventory()[mark:]
        if r.audit is not None
    ]


class TestRealArtifacts:
    def test_dropped_donation_detected(self, capture):
        import jax.numpy as jnp

        from d9d_tpu.telemetry import tracked_jit

        introspect, mark = capture

        def f(x, dead):
            return x + 1.0  # `dead` has no matching output to alias

        tj = tracked_jit(f, name="fix/undonated", donate_argnums=(1,))
        tj(jnp.ones((4, 4)), jnp.ones((7,)))
        (facts,) = _facts_since(introspect, mark)
        assert facts["donated_declared"] == 1
        assert facts["aliased_pairs"] == 0
        report = run_rules([facts], manifest())
        assert rules_of(report) == ["D9D101"]

    def test_full_donation_clean(self, capture):
        import jax.numpy as jnp

        from d9d_tpu.telemetry import tracked_jit

        introspect, mark = capture
        tj = tracked_jit(
            lambda x: x + 1.0, name="fix/donated", donate_argnums=(0,)
        )
        tj(jnp.ones((4, 4)))
        (facts,) = _facts_since(introspect, mark)
        assert facts["donated_declared"] == 1
        assert facts["aliased_pairs"] == 1
        assert run_rules([facts], manifest()).violations == []

    def test_baked_constant_closure_detected(self, capture):
        import jax.numpy as jnp

        from d9d_tpu.telemetry import tracked_jit

        introspect, mark = capture
        baked = np.ones((128, 128), np.float32)  # 64 KiB > threshold

        def f(x):
            return x @ jnp.asarray(baked)

        tj = tracked_jit(f, name="fix/baked")
        tj(jnp.ones((2, 128)))
        (facts,) = _facts_since(introspect, mark)
        assert facts["n_consts"] == 1
        assert facts["consts"][0]["bytes"] == 128 * 128 * 4
        report = run_rules([facts], manifest())
        assert rules_of(report) == ["D9D102"]

    def test_collective_bearing_fake_serve_step(self, capture):
        import jax
        import jax.numpy as jnp
        from jax.sharding import (
            Mesh,
            NamedSharding,
            PartitionSpec as P,
        )

        from d9d_tpu.telemetry import audit_capture, tracked_jit

        introspect, mark = capture
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

        def fake_step(x, y):
            g = jax.lax.with_sharding_constraint(
                x * 2.0 + 1.0, NamedSharding(mesh, P("dp"))
            )
            p = jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, P())
            )
            return p + y

        with audit_capture.context("serve"):
            tj = tracked_jit(fake_step, name="serve/step")
            rep = NamedSharding(mesh, P())
            tj(
                jax.device_put(jnp.ones((8, 4)), rep),
                jax.device_put(jnp.ones((8, 4)), rep),
            )
        (facts,) = _facts_since(introspect, mark)
        assert facts["context"] == "serve"
        assert facts["collectives"], "expected a collective in the HLO"
        exp = {"serve": {"serve/step": {"no_collectives": True}}}
        report = run_rules([facts], manifest(exp))
        assert rules_of(report) == ["D9D100"]

    def test_host_callback_detected(self, capture):
        import jax
        import jax.numpy as jnp

        from d9d_tpu.telemetry import tracked_jit

        introspect, mark = capture

        def f(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype),
                x,
            )
            return y + 1
        # host-callback payloads are allowed in COLD paths; this fixture
        # deliberately puts one in a tracked executable to pin detection
        tj = tracked_jit(f, name="fix/callback")
        tj(jnp.ones((4,)))
        (facts,) = _facts_since(introspect, mark)
        assert facts["callbacks"]
        report = run_rules([facts], manifest())
        assert rules_of(report) == ["D9D104"]

    def test_f64_census_from_jaxpr(self):
        """f64 detection at the jaxpr layer (no x64 compile needed):
        the census walks sub-jaxprs, so an f64 inside a scan body is
        seen too."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from d9d_tpu.telemetry.audit_capture import _jaxpr_census

        with jax.enable_x64(True):
            def body(c, _):
                return c * np.float64(1.5), None

            def f(x):
                out, _ = lax.scan(body, x, None, length=3)
                return out

            jaxpr = jax.make_jaxpr(f)(np.ones((4,), np.float64))
        census = _jaxpr_census(jaxpr)
        assert census["f64_ops"]
        report = run_rules([fact(**{
            "f64_ops": census["f64_ops"],
        })], manifest())
        assert rules_of(report) == ["D9D103"]
        # and the default f32 path is f64-free
        jaxpr32 = jax.make_jaxpr(lambda x: x * 2.0)(
            np.ones((4,), np.float32)
        )
        assert _jaxpr_census(jaxpr32)["f64_ops"] == []

    def test_capture_is_opt_in_and_compile_time_only(self):
        import jax
        import jax.numpy as jnp

        from d9d_tpu.telemetry import audit_capture, introspect, tracked_jit

        # opt-in: no facts without the flag
        audit_capture.enable(False)
        try:
            mark = len(introspect.inventory())
            tj = tracked_jit(lambda x: x * 2, name="fix/optout")
            tj(jnp.ones((4,)))
            recs = introspect.inventory()[mark:]
            assert len(recs) == 1 and recs[0].audit is None
        finally:
            audit_capture.enable(None)

        # compile-time only: with capture on, repeated calls reuse the
        # ONE compiled executable (no re-trace, no readback — the call
        # runs under a device→host transfer guard to prove it)
        audit_capture.enable(True)
        try:
            mark = len(introspect.inventory())
            tj = tracked_jit(lambda x: x * 3, name="fix/zerocost")
            x = jnp.ones((4,))
            tj(x)  # compile + capture happen here
            with jax.transfer_guard_device_to_host("disallow"):
                out = tj(x)
            jax.block_until_ready(out)
            recs = introspect.inventory()[mark:]
            assert len(recs) == 1
            assert recs[0].audit is not None
            assert recs[0].calls == 2
        finally:
            audit_capture.enable(None)

    def test_facts_are_json_serializable(self, capture):
        import jax.numpy as jnp

        from d9d_tpu.telemetry import tracked_jit

        introspect, mark = capture
        tj = tracked_jit(lambda x: x.sum(), name="fix/json")
        tj(jnp.ones((4, 4)))
        (facts,) = _facts_since(introspect, mark)
        assert json.loads(json.dumps(facts)) == facts


class TestReviewHardening:
    def test_same_shape_consts_get_distinct_fingerprints(self):
        """Two distinct over-threshold consts sharing dtype+shape must
        not collapse to one fingerprint — one baseline entry would
        otherwise cover any number of smuggled same-shape arrays."""
        c = {"bytes": 400_000, "shape": [100, 1000], "dtype": "float32"}
        report = run_rules(
            [fact(consts=[dict(c), dict(c)], n_consts=2)], manifest()
        )
        assert rules_of(report) == ["D9D102", "D9D102"]
        fps = {v.fingerprint() for v in report.violations}
        assert len(fps) == 2

    def test_write_baseline_refused_on_partial_runs(self, capsys):
        """--write-baseline with --legs/--facts would rebuild the
        baseline from a partial capture, erasing the other contexts'
        entries and their hand-written reasons (the d9d-lint --select
        refusal, one layer down)."""
        from tools.audit.cli import main

        assert main(["--legs", "serve", "--write-baseline"]) == 2
        err = capsys.readouterr().err
        assert "refuses" in err
        assert main(
            ["--facts", "whatever.jsonl", "--write-baseline"]
        ) == 2

    def test_census_counts_async_and_variadic_collectives(self):
        """Async (-start/-done pairs, tuple result types with spaces)
        and variadic collectives must census correctly — on TPU HLO the
        async form is the norm, and undercounting reads as 'no
        collectives' (verified miss before the type-match fix)."""
        from d9d_tpu.telemetry.audit_capture import _collective_census

        hlo = "\n".join([
            "HloModule jit_f",
            "  %ag = (f32[2]{0}, f32[4]{0}) all-gather-start(f32[2]{0} %p), dimensions={0}",
            "  %agd = f32[4]{0} all-gather-done((f32[2]{0}, f32[4]{0}) %ag)",
            "  %ar = (f32[4]{0}, f32[8]{0}) all-reduce(f32[4]{0} %a, f32[8]{0} %b), to_apply=%add",
            "  %rs = f32[2]{0} reduce-scatter(f32[4]{0} %c), dimensions={0}",
            "  ROOT %r = f32[4]{0} add(f32[4]{0} %agd, f32[4]{0} %ar)",
        ])
        assert _collective_census(hlo) == {
            "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
        }

    def test_manifest_rejects_fingerprintless_baseline_entry(
        self, tmp_path
    ):
        """A hand-edited entry that drops its fingerprint must be an
        rc-2 manifest error at load, not a KeyError downstream."""
        p = tmp_path / "AUDIT_BASELINE.json"
        p.write_text(json.dumps({
            "version": 1, "expectations": {},
            "baseline": [{"rule": "D9D101", "reason": "a fine reason"}],
        }))
        with pytest.raises(manifest_mod.AuditManifestError):
            manifest_mod.load(p)

    def test_census_tolerates_tpu_tiled_layout_tuple_types(self):
        """TPU optimized HLO prints tiled-layout annotations with
        NESTED parens inside async tuple types — the census must still
        see the op (a drifted chip schedule must not read as 'no
        collectives')."""
        from d9d_tpu.telemetry.audit_capture import _collective_census

        hlo = (
            "%ag = (bf16[1024,8192]{1,0:T(8,128)(2,1)}, "
            "bf16[8192,8192]{1,0:T(8,128)}) "
            "all-gather-start(bf16[1024,8192]{1,0:T(8,128)} %p), "
            "dimensions={0}"
        )
        assert _collective_census(hlo) == {"all-gather": 1}

    def test_cli_full_run_fails_on_unchecked_context(
        self, monkeypatch, capsys
    ):
        """On a FULL harness run (no --legs/--facts) an expectation
        context with zero captured facts is a dropped/renamed leg
        retiring its whole contract table — rc 1, not a note."""
        import tools.audit.harness as harness_mod
        from tools.audit.cli import main

        monkeypatch.setattr(
            harness_mod, "trace_registered_executables",
            lambda legs=None: [fact(context="train")],
        )
        import json as _json
        import pathlib
        import tempfile

        p = pathlib.Path(tempfile.mkdtemp()) / "m.json"
        p.write_text(_json.dumps({
            "version": 1,
            "expectations": {
                "train": {"x/step": {"no_collectives": True}},
                "spec_decode": {"serve/spec_round": {
                    "no_collectives": True,
                }},
            },
            "baseline": [],
        }))
        assert main(["--baseline", str(p)]) == 1
        out = capsys.readouterr().out
        assert "FULL harness run" in out
        # the same gap on an explicit partial run is a note, rc 0
        assert main(["--baseline", str(p), "--legs", "train"]) == 0
        assert "partial run" in capsys.readouterr().out

    def test_trace_failure_keeps_tracked_path(self, monkeypatch):
        """A capture-only trace() failure must not trip the permanent
        plain-jit fallback: compile accounting stays, only the audit
        facts are omitted."""
        import jax.numpy as jnp

        from d9d_tpu.telemetry import audit_capture, introspect, tracked_jit

        audit_capture.enable(True)
        try:
            mark = len(introspect.inventory())
            tj = tracked_jit(lambda x: x + 1, name="fix/tracefail")
            real = tj._jit

            class _QuirkyJit:
                # trace() raises where the plain lower() succeeds —
                # the capture-specific failure mode under test
                def trace(self, *a, **k):
                    raise RuntimeError("capture-path quirk")

                def __getattr__(self, name):
                    return getattr(real, name)

                def __call__(self, *a, **k):
                    return real(*a, **k)

            tj._jit = _QuirkyJit()
            out = tj(jnp.ones((4,)))
            assert float(out[0]) == 2.0
            recs = introspect.inventory()[mark:]
            assert len(recs) == 1, "compile accounting must survive"
            # the jaxpr-derived blocks degrade to empty; the HLO-derived
            # facts (collectives, aliasing) still land off the plain
            # lower() path
            assert recs[0].audit is not None
            assert recs[0].audit["dtype_ops"] == {}
            assert recs[0].audit["collectives"] == {}
            assert not tj._fallback, (
                "capture failure must not degrade the tracked path"
            )
        finally:
            audit_capture.enable(None)

    def test_print_audit_names_omitted_rows(self, capsys):
        from pathlib import Path

        from tools.trace_summary import print_audit

        evs = [
            (Path("x.jsonl"), {"name": f"e{i}", "audit": fact()})
            for i in range(5)
        ]
        print_audit(evs, top=1)
        out = capsys.readouterr().out
        assert "+3 more" in out
