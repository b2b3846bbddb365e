"""Static-analysis subsystem tests: lint rules (positive + negative
fixtures per rule), jaxpr/HLO program audits against synthetic violations
of each invariant and against the real tiny-config RoundRunner programs,
budget baseline round-trips, the CLI gate's exit codes, and the telemetry
sink materialization regression."""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.findings import (Baseline, Report, assign_fingerprints,
                                     make_finding)
from repro.analysis.jaxpr_audit import (audit_fn, compiled_alias_pairs,
                                        entry_output_arity, find_callbacks,
                                        find_dtypes)
from repro.analysis.lints import lint_file


# ---------------------------------------------------------------------------
# lint-rule fixtures
# ---------------------------------------------------------------------------

def lint_source(tmp_path, source, relpath="src/repro/somefile.py"):
    """Write ``source`` at ``relpath`` under a synthetic repo root and lint
    that one file."""
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return lint_file(str(tmp_path), str(path))


def rules_of(findings):
    return sorted(f.rule for f in findings)


def test_prng_key_reuse_positive(tmp_path):
    findings = lint_source(tmp_path, """
        import jax

        def f(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a + b
        """)
    assert rules_of(findings) == ["prng-key-reuse"]
    assert "key" in findings[0].message


def test_prng_key_reuse_negative_split(tmp_path):
    findings = lint_source(tmp_path, """
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (3,))
            b = jax.random.uniform(k2, (3,))
            return a + b

        def g(key, n):
            out = []
            for i in range(n):
                key, sub = jax.random.split(key)
                out.append(jax.random.normal(sub, (3,)))
            return out
        """)
    assert findings == []


def test_prng_key_reuse_branches_are_exclusive(tmp_path):
    findings = lint_source(tmp_path, """
        import jax

        def f(key, flag):
            if flag:
                a = jax.random.normal(key, (3,))
            else:
                a = jax.random.uniform(key, (3,))
            return a
        """)
    assert findings == []


def test_prng_key_reuse_across_loop_iterations(tmp_path):
    findings = lint_source(tmp_path, """
        import jax

        def f(key):
            out = []
            for _ in range(3):
                out.append(jax.random.normal(key, (3,)))
            return out
        """)
    assert rules_of(findings) == ["prng-key-reuse"]


def test_hidden_host_sync_positive(tmp_path):
    findings = lint_source(tmp_path, """
        import numpy as np

        def f(x):
            a = float(x)
            b = x.item()
            c = np.asarray(x)
            return a, b, c
        """, relpath="src/repro/core/engine.py")
    assert rules_of(findings) == ["hidden-host-sync"] * 3


def test_hidden_host_sync_negative(tmp_path):
    # whitelisted fetch helpers produce host values; other files are out of
    # the rule's scope entirely
    source = """
        import numpy as np
        from repro.selection import unpack_fetch

        def f(stacked):
            vec = unpack_fetch(np.asarray(stacked))
            return [float(v) for v in vec]
        """
    in_scope = lint_source(tmp_path, source,
                           relpath="src/repro/core/engine.py")
    # the np.asarray fetch itself is flagged (baseline territory); the
    # float() over the already-fetched values is not
    assert rules_of(in_scope) == ["hidden-host-sync"]
    assert "asarray" in in_scope[0].message
    out_of_scope = lint_source(tmp_path, """
        def f(x):
            return float(x)
        """, relpath="src/repro/launch/other.py")
    assert out_of_scope == []


def test_wall_clock_positive_and_exemption(tmp_path):
    source = """
        import time

        def f():
            return time.time()
        """
    assert rules_of(lint_source(tmp_path, source)) == ["wall-clock"]
    assert lint_source(tmp_path, source,
                       relpath="src/repro/telemetry/provenance.py") == []


def test_wall_clock_negative_perf_counter(tmp_path):
    findings = lint_source(tmp_path, """
        import time

        def f():
            return time.perf_counter()
        """)
    assert findings == []


def test_unseeded_np_random_positive(tmp_path):
    findings = lint_source(tmp_path, """
        import numpy as np

        NOISE = np.random.randn(4)
        """)
    assert rules_of(findings) == ["unseeded-np-random"]


def test_unseeded_np_random_negative(tmp_path):
    findings = lint_source(tmp_path, """
        import numpy as np

        rng = np.random.default_rng(0)
        NOISE = rng.normal(size=4)

        def f():
            return np.random.rand()  # function scope: not a module-load draw
        """)
    assert findings == []


def test_mutable_default_arg_positive(tmp_path):
    findings = lint_source(tmp_path, """
        def f(x, acc=[]):
            acc.append(x)
            return acc

        def g(x, table={}):
            return table
        """)
    assert rules_of(findings) == ["mutable-default-arg"] * 2


def test_mutable_default_arg_negative(tmp_path):
    findings = lint_source(tmp_path, """
        def f(x, acc=None, n=3, name="x"):
            acc = [] if acc is None else acc
            return acc
        """)
    assert findings == []


def test_parse_error_is_a_finding(tmp_path):
    findings = lint_source(tmp_path, "def broken(:\n")
    assert rules_of(findings) == ["parse-error"]


# ---------------------------------------------------------------------------
# findings engine: fingerprints + baseline
# ---------------------------------------------------------------------------

def test_fingerprint_survives_line_shift(tmp_path):
    body = """
        import time

        def f():
            return time.time()
        """
    a = lint_source(tmp_path, body, relpath="src/repro/a.py")
    shifted = "# one\n# two\n# three\n" + textwrap.dedent(body)
    b = lint_source(tmp_path, shifted, relpath="src/repro/a.py")
    a, b = assign_fingerprints(a), assign_fingerprints(b)
    assert a[0].line != b[0].line
    assert a[0].fingerprint == b[0].fingerprint


def test_duplicate_context_lines_get_distinct_fingerprints(tmp_path):
    findings = assign_fingerprints(lint_source(tmp_path, """
        import time

        def f():
            return time.time()

        def g():
            return time.time()
        """))
    assert len(findings) == 2
    assert findings[0].fingerprint != findings[1].fingerprint


def test_baseline_roundtrip_and_justification_enforcement(tmp_path):
    f1 = make_finding("wall-clock", "error", "src/repro/a.py", 4,
                      "msg", context="return time.time()")
    f2 = make_finding("wall-clock", "error", "src/repro/b.py", 9,
                      "msg", context="return time.time()")
    path = str(tmp_path / "lint_baseline.json")
    base = Baseline(path=path)
    base.add(f1, "intentional: wall-clock stamp for the run manifest")
    base.save()

    loaded = Baseline.load(path)
    assert loaded.suppresses(f1) and not loaded.suppresses(f2)

    report = Report(findings=[f1, f2], baseline=loaded)
    assert [f.fingerprint for f in report.open_findings] == [f2.fingerprint]

    # stripping the justification turns the suppression itself into a finding
    doc = json.load(open(path))
    doc["suppressions"][0]["justification"] = ""
    json.dump(doc, open(path, "w"))
    report = Report(findings=[f1, f2], baseline=Baseline.load(path))
    assert sorted(f.rule for f in report.open_findings) == [
        "unjustified-suppression", "wall-clock"]


def test_baseline_stale_detection(tmp_path):
    f1 = make_finding("wall-clock", "error", "src/repro/gone.py", 1, "msg",
                      context="time.time()")
    base = Baseline(path=str(tmp_path / "b.json"))
    base.add(f1, "why")
    assert base.stale([]) and base.stale([f1]) == []


# ---------------------------------------------------------------------------
# jaxpr/HLO audits: synthetic violations of each invariant
# ---------------------------------------------------------------------------

def test_audit_clean_function_passes():
    def clean(theta, x):
        return theta * x, jnp.sum(x)

    x = jnp.arange(4, dtype=jnp.float32)
    audit = audit_fn(clean, (x, x), name="t/clean", donate_argnums=(0,),
                     expected_donated=1, expected_fetch_leaves=1)
    assert audit.findings == []
    assert audit.donated_inputs == 1
    assert audit.aliased_outputs == 1
    assert audit.fetch_leaves == 1


def test_audit_flags_f64_weak_promotion():
    def leaky(x):
        # dtype=float is float64 once x64 is enabled: the classic weak leak
        return x + jnp.arange(x.shape[0], dtype=float)

    x = jnp.arange(4, dtype=jnp.float32)
    audit = audit_fn(leaky, (x,), name="t/leak", expected_fetch_leaves=1)
    assert "f64-in-program" in {f.rule for f in audit.findings}
    assert any("x64" in f.context for f in audit.findings)
    assert not jax.config.jax_enable_x64  # the retrace must not leak state


def test_audit_flags_host_callback():
    def chatty(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    x = jnp.arange(4, dtype=jnp.float32)
    audit = audit_fn(chatty, (x,), name="t/cb", expected_fetch_leaves=1,
                     x64_retrace=False)
    assert "host-callback-in-program" in {f.rule for f in audit.findings}


def test_audit_flags_lost_donation():
    def update(theta, x):
        return theta + x, jnp.sum(x)

    x = jnp.arange(4, dtype=jnp.float32)
    # donation intent says 1 carry leaf, but nothing is donated
    audit = audit_fn(update, (x, x), name="t/nodonate", donate_argnums=(),
                     expected_donated=1, expected_fetch_leaves=1)
    rules = {f.rule for f in audit.findings}
    # losing donation also breaks the fetch contract (the un-aliased carry
    # leaf becomes an extra fetched output)
    assert rules == {"donation-mismatch", "fetch-contract"}


def test_audit_flags_extra_fetch():
    def update(theta, x):
        # two non-aliased outputs where the contract pins one
        return theta + x, jnp.sum(x), jnp.max(x)

    x = jnp.arange(4, dtype=jnp.float32)
    audit = audit_fn(update, (x, x), name="t/extrafetch", donate_argnums=(0,),
                     expected_donated=1, expected_fetch_leaves=1)
    assert "fetch-contract" in {f.rule for f in audit.findings}
    assert audit.fetch_leaves == 2


def test_compiled_header_parsers():
    text = ("HloModule jit_f, is_scheduled=true, input_output_alias={ "
            "{0}: (0, {}, may-alias), {1}: (3, {}, may-alias) }, "
            "entry_computation_layout={(f32[2]{0}, f32[3,4]{1,0})->"
            "(f32[2]{0}, f32[3,4]{1,0}, f32[7]{0})}")
    assert compiled_alias_pairs(text) == [(0, 0), (1, 3)]
    assert entry_output_arity(text) == 3


def test_find_dtypes_descends_into_subjaxprs():
    def scanned(x):
        def body(c, _):
            return c, c.astype(np.float64) * np.float64(2.0)

        return jax.lax.scan(body, x, None, length=3)

    with jax.enable_x64(True):
        jx = jax.make_jaxpr(scanned)(jnp.float32(0.0))
    assert find_dtypes(jx)  # the f64 mul lives inside the scan body jaxpr
    assert find_callbacks(jx) == []


# ---------------------------------------------------------------------------
# real tiny-config RoundRunner program audit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def analysis_ctx():
    from repro.analysis.programs import build_context
    return build_context()


def test_tiny_accept_program_is_clean(analysis_ctx):
    from repro.analysis.programs import CELLS, expected_counts
    cell = next(c for c in CELLS if c.name == "pigeon/accept@vmap")
    runner, (fn, args, donate) = cell.realize(analysis_ctx)
    expected_donated, expected_fetch = expected_counts(fn, args, donate)
    audit = audit_fn(fn, args, name=cell.name, donate_argnums=donate,
                     expected_donated=expected_donated,
                     expected_fetch_leaves=expected_fetch,
                     lowered=runner.lower("accept", *args))
    assert audit.findings == []
    theta_leaves = len(jax.tree.leaves(analysis_ctx.theta))
    assert audit.donated_inputs == theta_leaves
    assert audit.aliased_outputs == theta_leaves
    assert audit.fetch_leaves == 1      # the single stacked round vector
    assert audit.transfers.get("outfeed", 0) == 0
    assert audit.transfers.get("host_callback", 0) == 0


def test_quant_kernel_cell_is_clean(analysis_ctx):
    from repro.analysis.programs import CELLS, expected_counts
    cell = next(c for c in CELLS if c.name == "kernels/quant_dequant@int8")
    _, (fn, args, donate) = cell.realize(analysis_ctx)
    _, expected_fetch = expected_counts(fn, args, donate)
    audit = audit_fn(fn, args, name=cell.name,
                     expected_fetch_leaves=expected_fetch)
    assert audit.findings == []
    assert audit.fetch_leaves == 2      # dequantized message + row scales


# ---------------------------------------------------------------------------
# budget baselines
# ---------------------------------------------------------------------------

def test_budget_roundtrip_and_mismatch(tmp_path):
    from repro.analysis.budgets import compare_budget, merge_budget
    path = str(tmp_path / "programs.json")
    measured = {"pigeon/accept@vmap": {"eqns": 100, "fetch_leaves": 1}}

    findings, _ = compare_budget(path, measured, "program-budget")
    assert [f.rule for f in findings] == ["program-budget-baseline-missing"]

    merge_budget(path, measured)
    findings, notes = compare_budget(path, measured, "program-budget")
    assert findings == [] and notes == []

    drifted = {"pigeon/accept@vmap": {"eqns": 100, "fetch_leaves": 2}}
    findings, _ = compare_budget(path, drifted, "program-budget")
    assert [f.rule for f in findings] == ["program-budget-mismatch"]
    assert findings[0].severity == "error"
    assert "fetch_leaves: 1 -> 2" in findings[0].message

    new_cell = {"pigeon/accept@vmap+policy": {"eqns": 7}}
    findings, _ = compare_budget(path, new_cell, "program-budget")
    assert [f.rule for f in findings] == ["program-budget-cell-missing"]


def test_budget_merge_preserves_other_device_counts(tmp_path):
    from repro.analysis.budgets import load_budget, merge_budget
    path = str(tmp_path / "compile_counts.json")
    merge_budget(path, {"sweep/block1@sharded@d8": {"new_programs": 1}})
    merge_budget(path, {"sweep/block1@sharded@d1": {"new_programs": 1}})
    cells = load_budget(path)["cells"]
    assert set(cells) == {"sweep/block1@sharded@d1",
                          "sweep/block1@sharded@d8"}


def test_budget_jax_version_mismatch_downgrades(tmp_path):
    from repro.analysis.budgets import compare_budget, merge_budget
    path = str(tmp_path / "programs.json")
    merge_budget(path, {"cell": {"eqns": 1}})
    doc = json.load(open(path))
    doc["meta"]["jax"] = "0.0.0"
    json.dump(doc, open(path, "w"))
    findings, notes = compare_budget(path, {"cell": {"eqns": 2}},
                                     "program-budget")
    assert findings and findings[0].severity == "warning"
    assert notes and "0.0.0" in notes[0]


def test_checked_in_budgets_cover_all_driver_cells():
    """The acceptance contract: compile-count and transfer-count baselines
    for every driver x placement x block cell are committed."""
    from repro.analysis.budgets import DRIVER_CELLS, budget_path
    from repro.analysis.findings import repo_root
    root = repo_root()
    compiles = json.load(open(budget_path(root, "compile_counts.json")))
    for name, _ in DRIVER_CELLS:
        for suffix in ("@vmap", "@sharded@d1", "@sharded@d8"):
            assert f"{name}{suffix}" in compiles["cells"], (name, suffix)
        again = [k for k in compiles["cells"] if k.startswith(f"{name}@")
                 and "-again" in name]
        for k in again:
            assert compiles["cells"][k]["new_signatures"] == 0, k
    programs = json.load(open(budget_path(root, "programs.json")))
    for cell in ("pigeon/accept@vmap", "pigeon/accept_block@vmap",
                 "pigeon/round@vmap", "splitfed/accept@vmap",
                 "sweep/sweep@vmap", "kernels/quant_dequant@int8"):
        row = programs["cells"][cell]
        assert row["outfeed"] == 0 and row["host_callback"] == 0
        if row["donated_inputs"]:
            assert row["aliased_outputs"] == row["donated_inputs"]


# ---------------------------------------------------------------------------
# CLI gate
# ---------------------------------------------------------------------------

def make_synthetic_repo(tmp_path, violate=True):
    src = tmp_path / "src" / "repro"
    src.mkdir(parents=True)
    body = "import time\n\n\ndef f():\n    return time.time()\n" if violate \
        else "import time\n\n\ndef f():\n    return time.perf_counter()\n"
    (src / "mod.py").write_text(body)
    return tmp_path


def test_cli_lints_gate_exit_codes(tmp_path, capsys):
    from repro.analysis.cli import run
    root = make_synthetic_repo(tmp_path, violate=True)
    out_json = str(tmp_path / "findings.json")
    rc = run(["--check", "--layers", "lints", "--root", str(root),
              "--json", out_json])
    assert rc == 1
    doc = json.load(open(out_json))
    assert [f["rule"] for f in doc["open"]] == ["wall-clock"]
    assert "provenance" in doc
    capsys.readouterr()

    # baselining the finding (with a justification) flips the gate to green
    base = Baseline(path=str(root / "analysis" / "lint_baseline.json"))
    base.add(make_finding(**{k: v for k, v in doc["open"][0].items()
                             if k in ("rule", "severity", "path", "line",
                                      "message", "context")}),
             "synthetic fixture")
    base.save()
    assert run(["--check", "--layers", "lints", "--root", str(root)]) == 0
    capsys.readouterr()


def test_cli_clean_tree_and_flag_validation(tmp_path, capsys):
    from repro.analysis.cli import run
    root = make_synthetic_repo(tmp_path, violate=False)
    assert run(["--check", "--layers", "lints", "--root", str(root)]) == 0
    assert run(["--check", "--update-baselines"]) == 2
    assert run(["--layers", "nope"]) == 2
    capsys.readouterr()


def test_repo_tree_lints_are_clean_or_baselined():
    """The PR's own tree passes the lint layer (the CI gate's fast half)."""
    from repro.analysis.cli import LINT_BASELINE
    from repro.analysis.findings import repo_root
    from repro.analysis.lints import run_lints
    root = repo_root()
    report = Report(findings=run_lints(root),
                    baseline=Baseline.load(os.path.join(root, LINT_BASELINE)))
    assert report.open_findings == [], [f.located()
                                        for f in report.open_findings]


# ---------------------------------------------------------------------------
# telemetry sink materialization (satellite: one fetch per event, up front)
# ---------------------------------------------------------------------------

class _CountingArray:
    """Array-like that counts host materializations and per-element syncs."""

    def __init__(self, arr):
        self.arr = np.asarray(arr)
        self.asarray_calls = 0
        self.item_calls = 0

    def __array__(self, dtype=None, copy=None):
        self.asarray_calls += 1
        return self.arr if dtype is None else self.arr.astype(dtype)

    def item(self):
        self.item_calls += 1
        return self.arr.item()


def test_materialize_fetches_each_array_once():
    from repro.telemetry.sinks import materialize
    vec = _CountingArray(np.arange(3.0, dtype=np.float32))
    scalar = _CountingArray(np.float32(0.5))
    event = {"event": "round", "val_losses": vec, "nested": [{"acc": scalar}],
             "t": 3, "name": "run", "flag": True, "none": None}
    out = materialize(event)
    assert out["val_losses"] == [0.0, 1.0, 2.0]
    assert out["nested"][0]["acc"] == 0.5
    assert (out["t"], out["name"], out["flag"], out["none"]) == \
        (3, "run", True, None)
    assert vec.asarray_calls == 1 and vec.item_calls == 0
    assert scalar.asarray_calls == 1 and scalar.item_calls == 0
    json.dumps(out)  # fully JSON-native, no default= needed


def test_materialize_handles_jax_and_numpy_types():
    from repro.telemetry.sinks import materialize
    event = {"a": jnp.arange(2, dtype=jnp.int32), "b": np.float32(1.5),
             "c": (np.int64(2), [np.bool_(True)]), "d": jnp.float32(0.25)}
    out = materialize(event)
    assert out == {"a": [0, 1], "b": 1.5, "c": [2, [True]], "d": 0.25}
    assert isinstance(out["b"], float) and isinstance(out["c"][0], int)


def test_jsonl_sink_materializes_before_encoding(tmp_path):
    from repro.telemetry.sinks import JSONLSink, read_jsonl
    vec = _CountingArray(np.arange(4.0, dtype=np.float32))
    path = str(tmp_path / "events.jsonl")
    sink = JSONLSink(path)
    sink.emit({"event": "round", "val_losses": vec, "t": 0})
    sink.close()
    assert vec.asarray_calls == 1 and vec.item_calls == 0
    events = read_jsonl(path)
    assert events == [{"event": "round",
                       "val_losses": [0.0, 1.0, 2.0, 3.0], "t": 0}]
