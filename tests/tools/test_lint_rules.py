"""Per-rule fixture coverage for the d9d-lint engine
(tools/lint/, docs/design/static_analysis.md).

One true-positive and one true-negative snippet per rule, plus the
suppression-comment semantics (reason mandatory → D9D000) and the
committed-baseline diff semantics (new vs baselined vs stale). The
snippets are tiny synthetic repos in tmp_path — the engine resolves
hot-path scopes and the observability doc relative to its root, so
fixtures exercise the exact production configuration paths.
"""

import textwrap

import pytest

from tools.lint import baseline as baseline_mod
from tools.lint.engine import lint_paths
from tools.lint.rules import (
    ALL_RULES,
    RULES_BY_ID,
)

DOC = textwrap.dedent(
    """
    # Observability

    | prefix | source | examples |
    |---|---|---|
    | `serve/*` | serving | `serve/ttft_s`, `serve/tokens` |
    | `slo/*` | slo | `slo/{policy}/burn` |
    | `train/*` | trainer | `train/phase/*` spans |
    """
)


def make_repo(tmp_path, files, doc=DOC):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src), encoding="utf-8")
    docp = tmp_path / "docs/design/observability.md"
    docp.parent.mkdir(parents=True, exist_ok=True)
    docp.write_text(doc, encoding="utf-8")
    return tmp_path


def run(tmp_path, rules=None, subdir="d9d_tpu"):
    rules = rules if rules is not None else list(ALL_RULES)
    return lint_paths(tmp_path, [tmp_path / subdir], rules)


# -- D9D001 ---------------------------------------------------------------


def test_d9d001_bare_jit_in_hot_module_fires(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/hot.py": """
            import functools
            import jax

            def g(x):
                return x

            f = jax.jit(g)

            @functools.partial(jax.jit, static_argnames=("k",))
            def h(x, k):
                return x
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D001"]])
    assert len(found) == 2
    assert {f.rule for f in found} == {"D9D001"}


def test_d9d001_tracked_jit_and_cold_modules_clean(tmp_path):
    make_repo(tmp_path, {
        # tracked_jit in a hot module: the sanctioned form
        "d9d_tpu/loop/hot.py": """
            from d9d_tpu.telemetry import tracked_jit

            def g(x):
                return x

            f = tracked_jit(g, name="loop/g")
        """,
        # bare jit OUTSIDE the hot-module surface: allowed
        "d9d_tpu/core/cold.py": """
            import jax

            def g(x):
                return x

            f = jax.jit(g)
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D001"]]) == []


# -- D9D002 ---------------------------------------------------------------


def test_d9d002_param_closure_fires(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/closure.py": """
            import jax

            def build(self):
                params = self.load()
                def step(x):
                    return params["w"] * x
                return jax.jit(step)

            def build_attr(self):
                def step(x):
                    return self._params["w"] * x
                return jax.jit(step)
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D002"]])
    msgs = "\n".join(f.message for f in found)
    assert len(found) == 2
    assert "closes over 'params'" in msgs
    assert "self._params" in msgs


def test_d9d002_traced_args_and_scan_bodies_clean(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/closure_ok.py": """
            import jax

            def build():
                def step(params, x):
                    return params["w"] * x
                return jax.jit(step)

            def scan_user(params, xs):
                # a scan BODY may close over params: it re-traces with
                # its enclosing jit, so the capture refreshes
                def body(c, x):
                    return c + params["w"] * x, x
                return jax.lax.scan(body, 0.0, xs)
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D002"]]) == []


# -- D9D003 ---------------------------------------------------------------


def test_d9d003_host_sync_in_registered_hot_scope_fires(tmp_path):
    # the file path matches the production hot-scope registration
    make_repo(tmp_path, {
        "d9d_tpu/loop/serve.py": """
            import jax
            import numpy as np

            class ContinuousBatcher:
                def _harvest_one(self):
                    toks_d = self._dispatch()
                    toks = np.asarray(toks_d)
                    loss = jax.numpy.sum(toks_d)
                    x = float(loss)
                    y = toks_d.item()
                    return toks, x, y
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D003"]])
    assert len(found) == 3  # np.asarray(from-call), float(device), .item()
    assert {f.rule for f in found} == {"D9D003"}


def test_d9d003_host_marshalling_and_cold_scopes_clean(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/serve.py": """
            import numpy as np

            class ContinuousBatcher:
                def _harvest_one(self):
                    # np.asarray on host lists is marshalling, not a sync
                    pos = np.asarray([s.pos for s in self._slots])
                    n = float(len(pos))
                    return pos, n

                def cold_debug_helper(self):
                    # not a registered hot scope: syncs allowed
                    return self._tokens.item()
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D003"]]) == []


# -- D9D004 ---------------------------------------------------------------


def test_d9d004_uncommitted_jit_init_fires(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/init_state.py": """
            import jax

            def build(opt, params):
                return jax.jit(opt.init)(params)
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D004"]])
    assert len(found) == 1
    assert "replicate_uncommitted" in found[0].message


def test_d9d004_normalized_inits_clean(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/init_state_ok.py": """
            import jax
            from d9d_tpu.core.tree_sharding import replicate_uncommitted

            def wrapped(opt, params, mesh):
                return replicate_uncommitted(jax.jit(opt.init)(params), mesh)

            def sharded(init_fn, shardings):
                return jax.jit(init_fn, out_shardings=shardings)()

            def named_then_normalized(opt, params, mesh):
                state = jax.jit(opt.init)(params)
                return replicate_uncommitted(state, mesh)
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D004"]]) == []


# -- D9D005 ---------------------------------------------------------------


def test_d9d005_nondeterminism_in_traced_fn_fires(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/chaos.py": """
            import time
            import numpy as np
            import jax

            def step(x):
                return x * time.time()

            jitted = jax.jit(step)

            def outer(xs):
                # traced transitively: scan body calls a helper that
                # draws host randomness
                def noise():
                    return np.random.rand()
                def body(c, x):
                    return c + noise(), x
                return jax.lax.scan(body, 0.0, xs)
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D005"]])
    assert len(found) == 2
    assert any("time.time" in f.message for f in found)
    assert any("numpy.random.rand" in f.message for f in found)


def test_d9d005_host_code_and_callback_escapes_clean(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/host_time.py": """
            import time
            import jax

            def host_loop(step, x):
                t0 = time.perf_counter()   # host telemetry: fine
                y = step(x)
                return y, time.perf_counter() - t0

            def traced_with_escape(x):
                # the callback payload runs on the HOST by contract
                jax.debug.callback(lambda v: print(time.time(), v), x)
                return x * 2

            jitted = jax.jit(traced_with_escape)
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D005"]]) == []


# -- D9D006 ---------------------------------------------------------------


def test_d9d006_undocumented_name_and_path_label_fire(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/telemetry/user.py": """
            def instrument(tele, batcher):
                tele.counter("serve/bogus_counter").add(1)
                batcher.set_replica_label("east/1")
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D006"]])
    assert len(found) == 2
    msgs = "\n".join(f.message for f in found)
    assert "serve/bogus_counter" in msgs
    assert "path-free-label" in msgs


def test_d9d006_documented_names_templates_and_probes_clean(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/telemetry/user_ok.py": """
            def instrument(tele, policies, batcher):
                tele.counter("serve/tokens").add(1)
                tele.observe("serve/ttft_s", 0.1)
                for p in policies:
                    tele.gauge(f"slo/{p.name}/burn").set(0.0)
                tele.span("train/phase/data_wait")
                batcher.set_replica_label("east1")
                # variable-named instruments are out of static reach
                name = compute_name()
                tele.counter(name).add(1)
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D006"]]) == []


# -- suppressions (engine, D9D000) ---------------------------------------


def test_suppression_with_reason_applies(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/sup.py": """
            import jax

            def g(x):
                return x

            # d9d-lint: disable=D9D001 — cold one-shot helper, test fixture
            f = jax.jit(g)
        """,
    })
    assert run(tmp_path) == []


def test_suppression_without_reason_files_d9d000(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/sup_bad.py": """
            import jax

            def g(x):
                return x

            f = jax.jit(g)  # d9d-lint: disable=D9D001
        """,
    })
    found = run(tmp_path)
    # the D9D001 is suppressed, but the reason-less comment is itself
    # a finding — discipline stays enforced
    assert [f.rule for f in found] == ["D9D000"]


def test_suppression_only_covers_named_rule(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/sup_other.py": """
            import jax

            def g(x):
                return x

            # d9d-lint: disable=D9D005 — wrong rule named
            f = jax.jit(g)
        """,
    })
    assert [f.rule for f in run(tmp_path)] == ["D9D001"]


# -- baseline diff semantics ---------------------------------------------


def _one_finding_repo(tmp_path):
    return make_repo(tmp_path, {
        "d9d_tpu/loop/hot.py": """
            import jax

            def g(x):
                return x

            f = jax.jit(g)
        """,
    })


def test_baseline_diff_new_baselined_stale(tmp_path):
    root = _one_finding_repo(tmp_path)
    findings = run(root, [RULES_BY_ID["D9D001"]])
    assert len(findings) == 1

    # accept the debt: the finding becomes baselined, the gate passes
    bl_path = tmp_path / "baseline.json"
    baseline_mod.write(bl_path, findings, root)
    diff = baseline_mod.diff_against_baseline(
        findings, baseline_mod.load(bl_path), root
    )
    assert diff.ok and len(diff.baselined) == 1 and not diff.stale

    # a NEW violation fails even though the old one is baselined
    hot = root / "d9d_tpu/loop/hot.py"
    hot.write_text(
        hot.read_text() + "\nf2 = jax.jit(lambda x: x)\n", encoding="utf-8"
    )
    findings2 = run(root, [RULES_BY_ID["D9D001"]])
    diff2 = baseline_mod.diff_against_baseline(
        findings2, baseline_mod.load(bl_path), root
    )
    assert not diff2.ok
    assert len(diff2.new) == 1 and len(diff2.baselined) == 1

    # fixing the baselined site leaves a STALE entry (reported, not fatal)
    hot.write_text(
        "import jax\n\ndef g(x):\n    return x\n\n"
        "f2 = jax.jit(lambda x: x)\n",
        encoding="utf-8",
    )
    findings3 = run(root, [RULES_BY_ID["D9D001"]])
    baseline_mod.write(bl_path, findings3, root)  # refresh accepts f2
    diff3 = baseline_mod.diff_against_baseline(
        findings3, baseline_mod.load(bl_path), root
    )
    assert diff3.ok and not diff3.stale and len(diff3.baselined) == 1


def test_baseline_fingerprint_survives_line_drift(tmp_path):
    root = _one_finding_repo(tmp_path)
    findings = run(root, [RULES_BY_ID["D9D001"]])
    bl_path = tmp_path / "baseline.json"
    baseline_mod.write(bl_path, findings, root)

    # insert unrelated lines ABOVE the finding: fingerprint must hold
    hot = root / "d9d_tpu/loop/hot.py"
    hot.write_text(
        "# a comment\n# another\n" + hot.read_text(), encoding="utf-8"
    )
    findings2 = run(root, [RULES_BY_ID["D9D001"]])
    diff = baseline_mod.diff_against_baseline(
        findings2, baseline_mod.load(bl_path), root
    )
    assert diff.ok and len(diff.baselined) == 1


# -- CLI ------------------------------------------------------------------


def test_cli_exit_codes_and_json(tmp_path, capsys):
    import json

    from tools.lint.cli import main

    root = _one_finding_repo(tmp_path)
    bl = tmp_path / "bl.json"

    # no baseline: the finding fails the gate
    rc = main(["--root", str(root), "--baseline", str(bl),
               "--json", "d9d_tpu"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1 and not report["ok"] and len(report["new"]) == 1

    # --write-baseline accepts it; the next run is clean
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--write-baseline", "d9d_tpu"]) == 0
    capsys.readouterr()
    rc = main(["--root", str(root), "--baseline", str(bl),
               "--json", "d9d_tpu"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["ok"] and report["new"] == []

    # --no-baseline ignores the acceptance
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--no-baseline", "d9d_tpu"]) == 1
    capsys.readouterr()

    # unknown rule id is a usage error
    assert main(["--select", "D9D999"]) == 2
    capsys.readouterr()


def test_cli_partial_run_cannot_corrupt_baseline(tmp_path, capsys):
    """--select + --write-baseline would erase the un-run rules'
    entries; --select alone must not report them as stale."""
    from tools.lint.cli import main

    root = make_repo(tmp_path, {
        "d9d_tpu/loop/two.py": """
            import time
            import jax

            def g(x):
                return x * time.time()

            f = jax.jit(g)
        """,
    })
    bl = tmp_path / "bl.json"
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--write-baseline", "d9d_tpu"]) == 0  # D9D001 + D9D005
    capsys.readouterr()

    # refusing the partial rewrite: rc 2, baseline untouched
    before = bl.read_text()
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--select", "D9D001", "--write-baseline", "d9d_tpu"]) == 2
    assert bl.read_text() == before
    capsys.readouterr()

    # a partial run: the D9D005 entry is unknown, NOT stale
    import json

    rc = main(["--root", str(root), "--baseline", str(bl),
               "--select", "D9D001", "--json", "d9d_tpu"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["ok"] and report["stale"] == []


def test_cli_nonexistent_target_is_an_error_not_clean(tmp_path, capsys):
    from tools.lint.cli import main

    root = make_repo(tmp_path, {"d9d_tpu/ok.py": "x = 1\n"})
    rc = main(["--root", str(root), "--baseline",
               str(tmp_path / "bl.json"), "no_such_dir"])
    out = capsys.readouterr()
    assert rc == 1
    assert "no such file or directory" in out.err


def test_cli_target_outside_root_is_an_error_not_a_traceback(
    tmp_path, capsys
):
    from tools.lint.cli import main

    root = make_repo(tmp_path / "root", {"d9d_tpu/ok.py": "x = 1\n"})
    outside = tmp_path / "elsewhere.py"
    outside.write_text("x = 1\n")
    rc = main(["--root", str(root), "--baseline",
               str(tmp_path / "bl.json"), str(outside)])
    err = capsys.readouterr().err
    assert rc == 1 and "outside the lint root" in err


def test_cli_write_baseline_refuses_on_analysis_errors(tmp_path, capsys):
    """A refresh over a partial scan must not silently drop entries
    for files the engine could not parse."""
    from tools.lint.cli import main

    root = _one_finding_repo(tmp_path)
    bl = tmp_path / "bl.json"
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--write-baseline", "d9d_tpu"]) == 0
    capsys.readouterr()
    before = bl.read_text()

    (root / "d9d_tpu/loop/broken.py").write_text("def f(:\n")
    rc = main(["--root", str(root), "--baseline", str(bl),
               "--write-baseline", "d9d_tpu"])
    err = capsys.readouterr().err
    assert rc == 2 and "refuses" in err and "syntax error" in err
    assert bl.read_text() == before  # untouched


def test_cli_missing_observability_doc_is_a_usage_error(tmp_path, capsys):
    from tools.lint.cli import main

    (tmp_path / "d9d_tpu").mkdir(parents=True)
    (tmp_path / "d9d_tpu/ok.py").write_text("x = 1\n")
    rc = main(["--root", str(tmp_path), "--baseline",
               str(tmp_path / "bl.json"), "d9d_tpu"])
    err = capsys.readouterr().err
    assert rc == 2 and "D9D006" in err
    # the other rules still run without the doc
    assert main(["--root", str(tmp_path), "--baseline",
                 str(tmp_path / "bl.json"), "--select",
                 "D9D001,D9D005", "d9d_tpu"]) == 0
    capsys.readouterr()


def test_cli_list_rules(capsys):
    from tools.lint.cli import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("D9D000", "D9D001", "D9D002", "D9D003", "D9D004",
                "D9D005", "D9D006"):
        assert rid in out


def test_d9d003_nested_helper_in_hot_scope_still_covered(tmp_path):
    """Wrapping a readback in a local def must not escape the rule."""
    make_repo(tmp_path, {
        "d9d_tpu/loop/serve.py": """
            import numpy as np

            class ContinuousBatcher:
                def _harvest_one(self):
                    def fetch():
                        toks_d = self._dispatch()
                        return np.asarray(toks_d)
                    return fetch()
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D003"]])
    assert len(found) == 1 and found[0].rule == "D9D003"


def test_d9d005_keyword_form_tracing_entries_covered(tmp_path):
    """scan(f=body, ...) / jit(fun=step) must seed the traced set."""
    make_repo(tmp_path, {
        "d9d_tpu/loop/kwform.py": """
            import time
            import jax

            def outer(xs):
                def body(c, x):
                    return c + time.time(), x
                return jax.lax.scan(f=body, init=0.0, xs=xs)
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D005"]])
    assert len(found) == 1 and "time.time" in found[0].message


def test_cli_non_python_file_target_is_an_error(tmp_path, capsys):
    from tools.lint.cli import main

    root = make_repo(tmp_path, {"d9d_tpu/ok.py": "x = 1\n"})
    (root / "README.md").write_text("# readme\n")
    rc = main(["--root", str(root), "--baseline",
               str(tmp_path / "bl.json"), "README.md"])
    err = capsys.readouterr().err
    assert rc == 1 and "not a Python file" in err


def test_cli_select_excludes_and_includes_d9d000(tmp_path, capsys):
    from tools.lint.cli import main

    root = make_repo(tmp_path, {
        "d9d_tpu/loop/sup_bad.py": """
            import jax

            def g(x):
                return x

            f = jax.jit(g)  # d9d-lint: disable=D9D001
        """,
    })
    bl = tmp_path / "bl.json"
    # selecting another rule must not fail on the reason-less
    # suppression (D9D001 itself is suppressed, reason or not)
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--select", "D9D001", "d9d_tpu"]) == 0
    capsys.readouterr()
    # but D9D000 is itself selectable
    assert main(["--root", str(root), "--baseline", str(bl),
                 "--select", "D9D000", "d9d_tpu"]) == 1
    out = capsys.readouterr().out
    assert "D9D000" in out


def test_syntax_error_reported_not_crash(tmp_path):
    root = make_repo(tmp_path, {
        "d9d_tpu/loop/broken.py": "def f(:\n",
    })
    errors = []
    findings = lint_paths(
        root, [root / "d9d_tpu"], list(ALL_RULES),
        on_error=lambda e: errors.append(str(e)),
    )
    assert findings == []
    assert len(errors) == 1 and "syntax error" in errors[0]

    with pytest.raises(Exception):
        lint_paths(root, [root / "d9d_tpu"], list(ALL_RULES))


# -- D9D007 (tracked_jit name uniqueness, cross-file) ---------------------


def test_d9d007_duplicate_literal_names_fire_across_files(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/a.py": """
            from d9d_tpu.telemetry import tracked_jit

            f = tracked_jit(lambda x: x, name="shared/step")
        """,
        "d9d_tpu/loop/b.py": """
            from d9d_tpu.telemetry import tracked_jit

            g = tracked_jit(lambda x: x + 1, name="shared/step")
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D007"]])
    # every site of the duplicated name is flagged, and each message
    # names the other collision sites
    assert len(found) == 2
    assert {f.rule for f in found} == {"D9D007"}
    assert {f.path for f in found} == {
        "d9d_tpu/loop/a.py", "d9d_tpu/loop/b.py",
    }
    assert all("shared/step" in f.message for f in found)
    assert all("a.py" in f.message and "b.py" in f.message for f in found)


def test_d9d007_identical_fstring_templates_fire(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/a.py": """
            from d9d_tpu.telemetry import tracked_jit

            def build(stage):
                return tracked_jit(lambda x: x, name=f"pp/s{stage}/update")

            def build2(stage):
                return tracked_jit(lambda x: x, name=f"pp/s{stage}/update")
        """,
    })
    found = run(tmp_path, [RULES_BY_ID["D9D007"]])
    # two SITES with the same template collide for every formatted
    # value — the blended-gauge bug the per-stage factories fixed
    assert len(found) == 2


def test_d9d007_distinct_names_and_single_factory_clean(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/a.py": """
            from d9d_tpu.telemetry import tracked_jit

            f = tracked_jit(lambda x: x, name="serve/step")
            g = tracked_jit(lambda x: x, name="serve/reset_row")

            def per_stage(sid, label):
                # ONE site formatted many ways is a single factory, not
                # a collision
                return tracked_jit(lambda x: x, name=f"pp_s{sid}/{label}")

            def dynamic(name):
                # non-static name: out of the rule's reach, never flagged
                return tracked_jit(lambda x: x, name=name)
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D007"]]) == []


def test_d9d007_suppression_with_reason_applies(tmp_path):
    make_repo(tmp_path, {
        "d9d_tpu/loop/a.py": """
            from d9d_tpu.telemetry import tracked_jit

            # d9d-lint: disable=D9D007 — deliberate share, one of the two is ever built
            f = tracked_jit(lambda x: x, name="shared/step")
            g = tracked_jit(lambda x: x, name="shared/step")  # d9d-lint: disable=D9D007 — deliberate share, one of the two is ever built
        """,
    })
    assert run(tmp_path, [RULES_BY_ID["D9D007"]]) == []


def test_d9d007_lint_file_single_file_still_checks(tmp_path):
    from tools.lint.engine import lint_file

    make_repo(tmp_path, {
        "d9d_tpu/loop/a.py": """
            from d9d_tpu.telemetry import tracked_jit

            f = tracked_jit(lambda x: x, name="shared/step")
            g = tracked_jit(lambda x: x, name="shared/step")
        """,
    })
    found = lint_file(
        tmp_path, tmp_path / "d9d_tpu/loop/a.py",
        [RULES_BY_ID["D9D007"]],
    )
    assert len(found) == 2


def test_rule_raised_linterror_routes_to_on_error(tmp_path):
    """A LintError raised by a rule's check() (not just a parse
    failure) reports via on_error and the scan continues — the
    documented no-raise contract library callers rely on."""
    from tools.lint.engine import LintError as LE

    root = make_repo(tmp_path, {
        "d9d_tpu/loop/a.py": "x = 1\n",
        "d9d_tpu/loop/b.py": "y = 2\n",
    })

    class ExplodingRule:
        rule_id = "D9DX99"
        summary = "always raises"

        @classmethod
        def check(cls, ctx):
            raise LE(f"{ctx.path}: rule blew up")
            yield  # pragma: no cover

    errors = []
    findings = lint_paths(
        root, [root / "d9d_tpu"], [ExplodingRule],
        on_error=lambda e: errors.append(str(e)),
    )
    assert findings == []
    assert len(errors) == 2  # every file reported, scan never aborted
    with pytest.raises(LE):
        lint_paths(root, [root / "d9d_tpu"], [ExplodingRule])
