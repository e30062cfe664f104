"""tools/jaxlint — the AST tracing-safety analyzer (tier-1).

Per-rule fixture snippets (one that must flag, one that must pass, one
exercising the inline suppression), the baseline workflow, the
``check_no_stray_jit`` shim, and the acceptance gate itself: the repo
tree is clean against the checked-in baseline.
"""

import importlib.util
import json
import pathlib
import sys
import textwrap

import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

from tools.jaxlint import REGISTRY, check_source, run_paths  # noqa: E402
from tools.jaxlint import baseline as baseline_mod           # noqa: E402
from tools.jaxlint.cli import main as jaxlint_main           # noqa: E402

#: a path inside an engine-scoped package, so every rule applies
HOT_PATH = "deeplearning4j_tpu/nn/fixture.py"


def fired(source, path=HOT_PATH):
    """Rule names flagged in ``source`` (dedented), in file order."""
    return [f.rule for f in check_source(textwrap.dedent(source), path)]


# ---------------------------------------------------------------------------
# framework
# ---------------------------------------------------------------------------

def test_rule_registry_ships_the_five_invariants():
    assert {"stray-jit", "use-after-donate", "host-sync-in-hot-path",
            "raw-shard-map", "impure-jit"} <= set(REGISTRY)
    assert len(REGISTRY) >= 5
    for rule in REGISTRY.values():
        assert rule.severity in ("error", "warning")
        assert rule.description


def test_no_regex_rule_implementations():
    """The framework contract: rules match ASTs, not strings — no `re`
    anywhere in the analyzer package."""
    import ast as ast_mod
    for path in sorted((REPO_ROOT / "tools" / "jaxlint").rglob("*.py")):
        tree = ast_mod.parse(path.read_text(), filename=str(path))
        for node in ast_mod.walk(tree):
            if isinstance(node, ast_mod.Import):
                assert not any(a.name == "re" for a in node.names), path
            elif isinstance(node, ast_mod.ImportFrom):
                assert node.module != "re", path


def test_standalone_comment_in_def_header_does_not_mute_function():
    """Only a directive TRAILING the def/decorator line covers the whole
    function; a full-line comment before the first statement means that
    spot, not a blanket mute."""
    src = '''
    import time

    def my_step(x):
        # jaxlint: disable=impure-jit — meant narrowly, not for the body
        t = time.time()
        r = time.perf_counter()
        return x + t + r
    '''
    # both time.* calls still flag (the standalone comment mutes nothing
    # since no finding is reported AT the comment's own line)
    assert fired(src, path="pkg/mod.py") == ["impure-jit"] * 2


def test_directive_must_lead_the_comment():
    """Prose MENTIONING the directive syntax mutes nothing — only a
    comment whose content IS the directive counts."""
    src = '''
    import time

    def my_step(x):
        t = time.time()  # TODO: the jaxlint: disable=impure-jit syntax exists
        return x + t
    '''
    assert fired(src, path="pkg/mod.py") == ["impure-jit"]


def test_suppression_covers_multiline_statement_closing_line():
    src = '''
    def my_step(x):
        z = float(
            x
        )  # jaxlint: disable=host-sync-in-hot-path — fixture
        return z
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_string_literals_never_suppress():
    src = '''
    import jax
    MSG = "# jaxlint: disable-file=stray-jit"
    f = jax.jit(lambda x: x)
    '''
    assert fired(src) == ["stray-jit"]


# ---------------------------------------------------------------------------
# stray-jit
# ---------------------------------------------------------------------------

def test_stray_jit_flags_raw_jit_and_import():
    src = '''
    import jax
    from jax import pjit

    @jax.jit
    def f(x):
        return x
    '''
    assert fired(src) == ["stray-jit", "stray-jit"]


def test_stray_jit_clean_through_engine():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    f = compile_cache.cached_jit(lambda x: x, label="fixture")
    '''
    assert fired(src) == []


def test_stray_jit_scoped_to_engine_packages():
    src = "import jax\nf = jax.jit(lambda x: x)\n"
    assert fired(src, path="deeplearning4j_tpu/models/fixture.py") == []
    assert fired(src, path="somewhere/else.py") == []
    assert fired(src, path="deeplearning4j_tpu/serving/f.py") \
        == ["stray-jit"]


def test_stray_jit_inline_suppression():
    src = '''
    import jax
    f = jax.jit(lambda x: x)  # jaxlint: disable=stray-jit — fixture
    '''
    assert fired(src) == []


def test_stray_jit_relative_paths_from_inside_package(tmp_path,
                                                      monkeypatch):
    """`cd deeplearning4j_tpu && jaxlint nn/` must still apply the
    scope — path matching normalizes against the cwd."""
    f = _violation_file(tmp_path)
    monkeypatch.chdir(tmp_path / "deeplearning4j_tpu")
    assert [x.rule for x in run_paths(["nn"])] == ["stray-jit"]


def test_suppression_list_tolerates_comma_space_and_reason():
    src = '''
    import time

    def my_step(x):  # jaxlint: disable=impure-jit, host-sync-in-hot-path — fixture
        t = time.time()
        return float(x) + t
    '''
    assert fired(src, path="pkg/mod.py") == []


# ---------------------------------------------------------------------------
# use-after-donate
# ---------------------------------------------------------------------------

def test_use_after_donate_flags_read_of_donated_buffer():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch)
        return params.sum()
    '''
    findings = check_source(textwrap.dedent(src), HOT_PATH)
    assert [f.rule for f in findings] == ["use-after-donate"]
    assert "'params'" in findings[0].message
    assert findings[0].line == 7  # the read, not the call


def test_use_after_donate_clean_when_rebound_from_result():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batches):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        for b in batches:
            params = step(params, b)
        return params
    '''
    assert fired(src) == []


def test_use_after_donate_kill_by_reassignment_then_read():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch)
        params = out
        return params.sum()
    '''
    assert fired(src) == []


def test_use_after_donate_sees_decorated_module_level_step():
    src = '''
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(x, s):
        return x + s

    def run(x, s):
        y = step(x, s)
        return s
    '''
    rules = fired(src, path="pkg/mod.py")  # outside stray-jit scope
    assert rules == ["use-after-donate"]


def test_use_after_donate_direct_call_form():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        out = compile_cache.cached_jit(body, donate_argnums=(0,))(
            params, batch)
        return params
    '''
    assert fired(src) == ["use-after-donate"]


def test_use_after_donate_same_statement_read_after_call():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch) + loss(params)
        return out
    '''
    assert fired(src) == ["use-after-donate"]


def test_use_after_donate_same_statement_read_before_call_clean():
    # left-to-right evaluation: loss(params) runs BEFORE the donation
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = loss(params) + step(params, batch)
        return out
    '''
    assert fired(src) == []


def test_use_after_donate_sees_class_method_bodies():
    src = '''
    import jax

    class Trainer:
        def fit(self, params, batch):
            step = jax.jit(body, donate_argnums=(0,))
            out = step(params, batch)
            return params.sum()
    '''
    assert fired(src, path="pkg/mod.py") == ["use-after-donate"]


def test_use_after_donate_non_donated_position_clean():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(1,))
        out = step(params, batch)
        return params.sum()
    '''
    assert fired(src) == []


def test_use_after_donate_metadata_reads_are_legal():
    """JAX deletes the donated BUFFER, not the aval — .shape/.ndim/
    .dtype reads after donation must not flag."""
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch)
        n = params.shape[0]
        return out, n, params.dtype
    '''
    assert fired(src) == []


def test_use_after_donate_conditional_rebind_keeps_taint():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch, flag):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch)
        if flag:
            params = out
        return compute(params)
    '''
    assert fired(src) == ["use-after-donate"]


def test_use_after_donate_sibling_branch_rebind_keeps_taint():
    """A rebind in a DIFFERENT if (same nesting depth) may not run on
    the path where the donation did — the taint must survive."""
    src = '''
    import jax

    def run(p, b, a, c):
        step = jax.jit(body, donate_argnums=(0,))
        if a:
            out = step(p, b)
        if c:
            p = fresh()
        return p
    '''
    assert fired(src, path="pkg/mod.py") == ["use-after-donate"]


def test_use_after_donate_unconditional_rebind_clears_taint():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch, flag):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch)
        params = out
        if flag:
            params = transform(params)
        return compute(params)
    '''
    assert fired(src) == []


def test_use_after_donate_rebound_to_plain_callable_clears_entry():
    src = '''
    import jax

    def fit(params, batch):
        step = jax.jit(body, donate_argnums=(0,))
        step = plain_fn
        out = step(params, batch)
        return params.sum()
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_use_after_donate_param_shadows_module_level_step():
    src = '''
    from functools import partial
    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def step(x):
        return x

    def run(step, params, batch):
        out = step(params, batch)
        return params.sum()
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_use_after_donate_sees_match_case_bodies():
    src = '''
    import jax

    def fit(params, batch, mode):
        step = jax.jit(body, donate_argnums=(0,))
        match mode:
            case 1:
                out = step(params, batch)
                extra = params + 1
        return out
    '''
    assert fired(src, path="pkg/mod.py") == ["use-after-donate"]


def test_use_after_donate_else_branch_is_mutually_exclusive():
    """A read in the other arm of the if holding the donating call runs
    only when the call didn't — never a use-after-donate."""
    src = '''
    import jax

    def fit(params, batch, cond):
        step = jax.jit(body, donate_argnums=(0,))
        if cond:
            out = step(params, batch)
            return out
        else:
            return params + 1
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_use_after_donate_suppression():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def fit(params, batch):
        step = compile_cache.cached_jit(body, donate_argnums=(0,))
        out = step(params, batch)
        return params.sum()  # jaxlint: disable=use-after-donate — fixture
    '''
    assert fired(src) == []


# ---------------------------------------------------------------------------
# host-sync-in-hot-path
# ---------------------------------------------------------------------------

def test_host_sync_flags_item_float_asarray_and_if_on_tracer():
    src = '''
    import numpy as np

    def train_step(params, x):
        if x:
            pass
        a = x.item()
        b = float(params)
        c = np.asarray(x)
        return a + b
    '''
    assert sorted(fired(src)) == ["host-sync-in-hot-path"] * 4


def test_host_sync_clean_on_pure_step_and_host_helpers():
    src = '''
    import jax.numpy as jnp

    def train_step(params, x):
        return jnp.sum(params * x)

    def host_report(score):
        return float(score)  # not a traced function — fine
    '''
    assert fired(src) == []


def test_host_sync_cast_of_host_scalar_in_hot_fn_is_clean():
    """float()/int() only fire when the argument reads a tracer param —
    a cast of a trace-time host value in a *_step function is fine."""
    src = '''
    def train_step(params, x):
        scale = float(get_config().lr)
        return params * scale * x
    '''
    assert fired(src) == []


def test_host_sync_cast_of_tracer_expression_flags():
    src = '''
    def train_step(params, x):
        return float((params * x).sum())
    '''
    assert fired(src) == ["host-sync-in-hot-path"]


def test_host_sync_respects_static_argnums_and_kwonly():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def body(params, n_epochs, *, use_bias):
        if n_epochs > 2:
            pass
        if use_bias:
            pass
        return params

    f = compile_cache.cached_jit(body, static_argnums=(1,))
    '''
    assert fired(src) == []


def test_host_sync_shape_branching_is_static_not_a_sync():
    """`if x.ndim == 1` / `if x.shape[0] > 1` specialize on STATIC
    trace-time metadata — the standard idiom, never a host sync."""
    src = '''
    def train_step(params, x):
        if x.ndim == 1:
            pass
        if x.shape[0] > 1 and params.dtype == "float32":
            pass
        if x.sum() > 0:       # a traced VALUE — still flagged
            pass
        return params
    '''
    assert fired(src) == ["host-sync-in-hot-path"]


def test_host_sync_factories_are_not_steps():
    src = '''
    def make_train_step(cfg):
        if cfg:
            n = int(cfg)
        return n
    '''
    assert fired(src) == []


def test_host_sync_def_line_suppression_covers_body():
    src = '''
    def time_step(fn):  # jaxlint: disable=host-sync-in-hot-path — harness
        a = float(fn)
        return a
    '''
    assert fired(src) == []


# ---------------------------------------------------------------------------
# raw-shard-map
# ---------------------------------------------------------------------------

def test_raw_shard_map_flags_every_import_spelling():
    src = '''
    from jax.experimental.shard_map import shard_map
    from jax import shard_map as smap
    import jax

    g = jax.experimental.shard_map.shard_map
    h = jax.shard_map
    '''
    assert fired(src, path="pkg/mod.py") == ["raw-shard-map"] * 4


def test_raw_shard_map_clean_via_compat():
    src = '''
    from deeplearning4j_tpu.compat import shard_map

    f = shard_map(lambda x: x, mesh=None, in_specs=(), out_specs=())
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_raw_shard_map_disable_file():
    src = '''
    # jaxlint: disable-file=raw-shard-map — this fixture is a shim too
    from jax.experimental.shard_map import shard_map
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_compat_module_carries_the_shim_annotation():
    text = (REPO_ROOT / "deeplearning4j_tpu" / "compat.py").read_text()
    assert "jaxlint: disable-file=raw-shard-map" in text


# ---------------------------------------------------------------------------
# impure-jit
# ---------------------------------------------------------------------------

def test_impure_jit_flags_time_print_nprandom_global_and_mutation():
    src = '''
    import time
    import numpy as np

    acc = []

    def outer():
        def my_step(x):
            global acc
            t = time.time()
            r = np.random.normal()
            print(x)
            acc.append(x)
            return x + t + r
        return my_step
    '''
    assert sorted(fired(src, path="pkg/mod.py")) == ["impure-jit"] * 5


def test_impure_jit_flags_np_random_random_itself():
    src = '''
    import numpy as np

    def my_step(x):
        return x + np.random.random()
    '''
    assert fired(src, path="pkg/mod.py") == ["impure-jit"]


def test_impure_jit_trace_time_local_containers_are_fine():
    src = '''
    def train_step(params, x):
        outs = []
        for p in params:
            outs.append(p * x)
        table = {}
        table["k"] = x
        return outs, table
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_impure_jit_only_fires_in_traced_functions():
    src = '''
    import time

    def wall_clock_report():
        return time.time()
    '''
    assert fired(src, path="pkg/mod.py") == []


def test_impure_jit_catches_fn_passed_to_cached_jit_by_name():
    src = '''
    import time
    from deeplearning4j_tpu.runtime import compile_cache

    def body(x):
        return x * time.time()

    f = compile_cache.cached_jit(body, label="fixture")
    '''
    assert fired(src, path="pkg/mod.py") == ["impure-jit"]


def test_impure_jit_suppression_names_only_that_rule():
    src = '''
    import time

    def my_step(x):
        t = time.time()  # jaxlint: disable=impure-jit — fixture
        return float(x)
    '''
    # the float() host sync is NOT covered by the impure-jit disable
    assert fired(src, path="pkg/mod.py") == ["host-sync-in-hot-path"]


# ---------------------------------------------------------------------------
# baseline workflow
# ---------------------------------------------------------------------------

def _violation_file(tmp_path, name="mod.py", extra=""):
    d = tmp_path / "deeplearning4j_tpu" / "nn"
    d.mkdir(parents=True, exist_ok=True)
    f = d / name
    f.write_text("import jax\nf = jax.jit(lambda x: x)\n" + extra)
    return f


def test_baseline_grandfathers_old_findings_only(tmp_path):
    f = _violation_file(tmp_path)
    bl = tmp_path / "baseline.json"
    findings = run_paths([f])
    assert [x.rule for x in findings] == ["stray-jit"]
    baseline_mod.save(bl, findings)

    # same tree: everything grandfathered, nothing new
    new, old = baseline_mod.apply(run_paths([f]), baseline_mod.load(bl))
    assert new == [] and len(old) == 1

    # a NEW violation is not hidden by the baseline
    f.write_text(f.read_text() + "g = jax.pjit(lambda x: x)\n")
    new, old = baseline_mod.apply(run_paths([f]), baseline_mod.load(bl))
    assert [x.rule for x in new] == ["stray-jit"] and len(old) == 1


def test_baseline_survives_line_number_churn(tmp_path):
    f = _violation_file(tmp_path)
    bl = tmp_path / "baseline.json"
    baseline_mod.save(bl, run_paths([f]))
    # shift the finding down two lines; fingerprints are text-based
    f.write_text("import os\nimport sys\n" + f.read_text())
    new, old = baseline_mod.apply(run_paths([f]), baseline_mod.load(bl))
    assert new == [] and len(old) == 1


def test_baseline_fingerprints_survive_path_spelling(tmp_path, monkeypatch):
    """Baseline written with a relative path must still grandfather the
    finding when jaxlint is later invoked with the absolute path."""
    f = _violation_file(tmp_path)
    bl = tmp_path / "baseline.json"
    monkeypatch.chdir(tmp_path)
    rel = f.relative_to(tmp_path)
    baseline_mod.save(bl, run_paths([rel]))
    new, old = baseline_mod.apply(run_paths([f.resolve()]),
                                  baseline_mod.load(bl))
    assert new == [] and len(old) == 1


def test_write_baseline_partial_scope_keeps_other_files(tmp_path):
    fa = _violation_file(tmp_path, "a.py")
    fb = _violation_file(tmp_path, "b.py")
    bl = tmp_path / "baseline.json"
    assert jaxlint_main([str(tmp_path), "--baseline", str(bl),
                         "--write-baseline"]) == 0
    # re-snapshot only a.py: b.py's grandfathered entry must survive
    assert jaxlint_main([str(fa), "--baseline", str(bl),
                         "--write-baseline"]) == 0
    assert jaxlint_main([str(tmp_path), "--baseline", str(bl)]) == 0
    # and --select snapshots are refused outright
    assert jaxlint_main([str(tmp_path), "--baseline", str(bl),
                         "--select", "stray-jit",
                         "--write-baseline"]) == 2


def test_cli_end_to_end_baseline_and_exit_codes(tmp_path, capsys):
    f = _violation_file(tmp_path)
    bl = tmp_path / "baseline.json"
    assert jaxlint_main([str(f), "--baseline", str(bl)]) == 1
    assert jaxlint_main([str(f), "--baseline", str(bl),
                         "--write-baseline"]) == 0
    assert jaxlint_main([str(f), "--baseline", str(bl)]) == 0
    out = capsys.readouterr().out
    assert "baselined" in out
    assert jaxlint_main([str(f), "--baseline", str(bl),
                         "--no-baseline"]) == 1


def test_cli_result_cache_round_trip(tmp_path, capsys):
    f = _violation_file(tmp_path)
    bl = tmp_path / "baseline.json"
    cache = tmp_path / "cache.json"
    assert jaxlint_main([str(f), "--baseline", str(bl),
                         "--cache-file", str(cache)]) == 1
    first = capsys.readouterr().out
    assert cache.exists() and json.loads(cache.read_text())
    assert jaxlint_main([str(f), "--baseline", str(bl),
                         "--cache-file", str(cache)]) == 1
    assert capsys.readouterr().out == first  # cached findings identical


def test_cli_cache_flag_does_not_swallow_paths(tmp_path, monkeypatch,
                                               capsys):
    """--cache is a bare flag: the paths after it must still be linted
    (an optional-argument form would eat the first one as a filename)."""
    f = _violation_file(tmp_path)
    monkeypatch.chdir(tmp_path)  # default cache file lands here
    assert jaxlint_main(["--cache", str(f), "--no-baseline"]) == 1
    assert "stray-jit" in capsys.readouterr().out
    assert (tmp_path / ".jaxlint_cache.json").exists()


def test_cli_corrupt_baseline_is_a_usage_error(tmp_path, capsys):
    f = _violation_file(tmp_path)
    bl = tmp_path / "baseline.json"
    bl.write_text("{not json")
    assert jaxlint_main([str(f), "--baseline", str(bl)]) == 2
    assert "baseline" in capsys.readouterr().err
    bl.write_text(json.dumps({"version": 99, "entries": []}))
    assert jaxlint_main([str(f), "--baseline", str(bl)]) == 2
    bl.write_text('"oops"')  # valid JSON, wrong shape
    assert jaxlint_main([str(f), "--baseline", str(bl)]) == 2


def test_cli_list_rules(capsys):
    assert jaxlint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in ("stray-jit", "use-after-donate", "host-sync-in-hot-path",
                 "raw-shard-map", "impure-jit"):
        assert name in out


# ---------------------------------------------------------------------------
# the shim + the acceptance gate
# ---------------------------------------------------------------------------

def _load_shim():
    spec = importlib.util.spec_from_file_location(
        "check_no_stray_jit", REPO_ROOT / "tools" / "check_no_stray_jit.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_shim_flags_planted_stray_jit(tmp_path):
    _violation_file(tmp_path)
    shim = _load_shim()
    findings = shim.find_stray_jits(tmp_path)
    assert len(findings) == 1
    assert findings[0].startswith("deeplearning4j_tpu/nn/mod.py:2:")


def test_repo_is_clean_against_checked_in_baseline():
    """The acceptance criterion, as a tier-1 test: the analyzer exits 0
    over the full scanned tree with the shipped baseline."""
    rc = jaxlint_main([str(REPO_ROOT / "deeplearning4j_tpu"),
                       str(REPO_ROOT / "tools")])
    assert rc == 0


def test_checked_in_baseline_is_empty():
    """Deliberate exceptions are annotated inline, not baselined — the
    shipped baseline carries no debt (ISSUE 4 satellite #1)."""
    data = json.loads(
        (REPO_ROOT / "tools" / "jaxlint" / "baseline.json").read_text())
    assert data["entries"] == []


# ---------------------------------------------------------------------------
# PR 10 framework: families, fingerprint, --jobs, --format json
# ---------------------------------------------------------------------------

def only(src, rule, path="pkg/mod.py"):
    """Lines at which exactly ``rule`` fired (other rules ignored — a
    divergent-branch fixture legitimately also trips host-sync)."""
    import textwrap
    return [f.line for f in check_source(textwrap.dedent(src), path)
            if f.rule == rule]


def test_registry_ships_both_new_families():
    collective = {"unbound-axis", "collective-in-divergent-branch",
                  "donation-across-collective"}
    concurrency = {"unlocked-shared-mutation", "blocking-under-lock",
                   "impure-signal-handler"}
    assert collective | concurrency <= set(REGISTRY)
    assert len(REGISTRY) >= 11
    for name in collective:
        assert REGISTRY[name].family == "collective"
    for name in concurrency:
        assert REGISTRY[name].family == "concurrency"
    for name in ("stray-jit", "use-after-donate", "impure-jit"):
        assert REGISTRY[name].family == "tracing"


def test_cli_list_rules_groups_by_family(capsys):
    assert jaxlint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for header in ("collective:", "concurrency:", "tracing:"):
        assert header in out
    for name in ("unbound-axis", "collective-in-divergent-branch",
                 "donation-across-collective", "unlocked-shared-mutation",
                 "blocking-under-lock", "impure-signal-handler"):
        assert name in out


def test_framework_fingerprint_covers_astutil_and_core(tmp_path):
    """The cache key must change when the SHARED framework changes, not
    only when a rule file does — a fix to the class-scoped lock
    tracking has to re-lint files whose text never moved."""
    import shutil
    from tools.jaxlint import core as core_mod

    pkg = REPO_ROOT / "tools" / "jaxlint"
    scratch = tmp_path / "jaxlint_copy"
    shutil.copytree(pkg, scratch,
                    ignore=shutil.ignore_patterns("__pycache__"))
    fp0 = core_mod._analyzer_fingerprint(scratch)
    assert fp0 == core_mod._analyzer_fingerprint(scratch)  # stable
    astutil_py = scratch / "astutil.py"
    astutil_py.write_text(astutil_py.read_text() + "\n# touched\n")
    fp1 = core_mod._analyzer_fingerprint(scratch)
    assert fp1 != fp0
    core_py = scratch / "core.py"
    core_py.write_text(core_py.read_text() + "\n# touched\n")
    fp2 = core_mod._analyzer_fingerprint(scratch)
    assert fp2 not in (fp0, fp1)


def test_result_cache_invalidates_on_framework_edit(tmp_path, monkeypatch):
    """A cache entry written under one analyzer fingerprint must be
    ignored once the fingerprint changes (regression: the key used to
    cover only the file source + rule names)."""
    from tools.jaxlint import core as core_mod

    f = _violation_file(tmp_path)
    cache = tmp_path / "cache.json"
    findings = run_paths([f], cache_path=cache)
    assert [x.rule for x in findings] == ["stray-jit"]
    entry = json.loads(cache.read_text())
    (key0,) = {v["key"] for v in entry.values()}

    # simulate a framework edit: poison the cached entry with bogus
    # findings, then flip the fingerprint — the poisoned entry must NOT
    # be served
    for v in entry.values():
        v["findings"] = []
    cache.write_text(json.dumps(entry))
    monkeypatch.setattr(core_mod, "_ANALYZER_FP", "deadbeef" * 8)
    findings = run_paths([f], cache_path=cache)
    assert [x.rule for x in findings] == ["stray-jit"]
    entry = json.loads(cache.read_text())
    (key1,) = {v["key"] for v in entry.values()}
    assert key1 != key0

    # same poisoning WITHOUT a fingerprint change is served from cache
    # (that's what a cache is) — proving the invalidation above really
    # came from the fingerprint
    for v in entry.values():
        v["findings"] = []
    cache.write_text(json.dumps(entry))
    assert run_paths([f], cache_path=cache) == []


def test_cli_jobs_output_is_deterministic(tmp_path, capsys):
    """--jobs N must not reorder findings: per-file results are
    stitched back in file order whatever the worker count."""
    for i in range(6):
        _violation_file(tmp_path, f"m{i}.py",
                        extra="g = jax.pjit(lambda x: x)\n")
    outs = []
    for jobs in ("1", "3", "8"):
        assert jaxlint_main([str(tmp_path), "--no-baseline",
                             "--jobs", jobs]) == 1
        outs.append(capsys.readouterr().out)
    assert outs[0] == outs[1] == outs[2]
    assert outs[0].count("stray-jit") == 12


def test_cli_jobs_rejects_nonpositive(capsys):
    assert jaxlint_main(["--jobs", "0", "pkg"]) == 2
    assert "--jobs" in capsys.readouterr().err


def test_cli_format_json_records_and_exit_codes(tmp_path, capsys):
    f = _violation_file(tmp_path)
    assert jaxlint_main([str(f), "--no-baseline",
                         "--format", "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is False and data["errors"] == 1
    (rec,) = data["findings"]
    assert rec["rule"] == "stray-jit" and rec["severity"] == "error"
    assert rec["file"].endswith("mod.py") and rec["line"] == 2
    assert rec["family"] == "tracing"
    assert isinstance(rec["col"], int) and rec["message"]

    # clean tree: ok object, exit 0, empty findings
    f.write_text("x = 1\n")
    assert jaxlint_main([str(f), "--no-baseline",
                         "--format", "json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is True and data["findings"] == []


def test_ci_runs_the_json_format_gate():
    text = (REPO_ROOT / "tools" / "ci.sh").read_text()
    assert "--format json" in text.split("telemetry")[0]


# ---------------------------------------------------------------------------
# unbound-axis
# ---------------------------------------------------------------------------

def test_unbound_axis_flags_literal_outside_vocabulary():
    src = '''
    from jax import lax

    def local_mean(x):
        return lax.pmean(x, "dta")
    '''
    assert only(src, "unbound-axis") == [5]


def test_unbound_axis_vocabulary_and_shard_map_bound_pass():
    src = '''
    import jax
    from jax import lax
    from deeplearning4j_tpu.compat import shard_map

    def body(x):
        return lax.psum(x, "data") + lax.pmean(x, "model")

    def ring(x):
        return lax.all_gather(x, "ring")

    f = jax.pmap(ring, axis_name="ring")
    '''
    assert only(src, "unbound-axis") == []


def test_unbound_axis_resolves_parameter_defaults():
    src = '''
    from jax import lax

    def reduce_it(x, axis="bogus"):
        return lax.psum(x, axis)

    def fine(x, axis="data"):
        return lax.psum(x, axis)

    def unknowable(x, axis):
        return lax.psum(x, axis)
    '''
    assert only(src, "unbound-axis") == [5]


def test_unbound_axis_resolves_local_constant_not_imports():
    src = '''
    from jax import lax
    from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

    MY_AXIS = "nowhere"

    def a(x):
        return lax.psum(x, MY_AXIS)

    def b(x):
        return lax.psum(x, DATA_AXIS)
    '''
    # the local constant resolves (and is unbound); the imported name is
    # the exporter's contract and stays silent
    assert only(src, "unbound-axis") == [8]


def test_unbound_axis_suppression():
    src = '''
    from jax import lax

    def local_mean(x):
        return lax.pmean(x, "ad-hoc")  # jaxlint: disable=unbound-axis — fixture
    '''
    assert only(src, "unbound-axis") == []


# ---------------------------------------------------------------------------
# collective-in-divergent-branch
# ---------------------------------------------------------------------------

def test_divergent_branch_flags_collective_under_tracer_if():
    src = '''
    from jax import lax

    def train_step(params, grads, loss):
        if loss > 3.0:
            grads = lax.psum(grads, "data")
        return grads
    '''
    assert only(src, "collective-in-divergent-branch") == [6]


def test_divergent_branch_post_psum_decision_passes():
    src = '''
    from jax import lax

    def train_step(params, grads, loss):
        gloss = lax.psum(loss, "data")
        if gloss > 3.0:
            grads = lax.psum(grads, "data")
        return grads
    '''
    # the branch decision flowed THROUGH a collective: replica-uniform,
    # exactly the PR 5 guard-skip pattern
    assert only(src, "collective-in-divergent-branch") == []


def test_divergent_branch_taint_propagates_through_locals():
    src = '''
    from jax import lax

    def train_step(params, batch):
        local_score = batch * 2.0
        while local_score > 0:
            params = lax.pmean(params, "data")
        return params
    '''
    assert only(src, "collective-in-divergent-branch") == [7]


def test_divergent_branch_only_in_hot_functions():
    src = '''
    from jax import lax

    def host_driver(flag, grads):
        if flag:
            return lax.psum(grads, "data")
        return grads
    '''
    assert only(src, "collective-in-divergent-branch") == []


def test_divergent_branch_suppression():
    src = '''
    from jax import lax

    def train_step(params, loss):
        if loss > 3.0:
            params = lax.pmean(params, "data")  # jaxlint: disable=collective-in-divergent-branch — fixture
        return params
    '''
    assert only(src, "collective-in-divergent-branch") == []


# ---------------------------------------------------------------------------
# donation-across-collective
# ---------------------------------------------------------------------------

def test_donation_across_collective_flags_builder_read_after():
    src = '''
    from deeplearning4j_tpu.parallel.sharded_fit import build_scanned_epochs

    def fit(step, mesh, params, ustate, batches, key):
        fn = build_scanned_epochs(step, mesh, label="fit")
        new_p, new_u, scores, skips = fn(params, ustate, batches, key, 0, 1)
        return params, scores
    '''
    assert only(src, "donation-across-collective") == [7]
    assert only(src, "use-after-donate") == []   # no double report


def test_donation_across_collective_rebind_and_donate_false_pass():
    src = '''
    from deeplearning4j_tpu.parallel.sharded_fit import (
        build_scanned_epochs, build_sharded_step)

    def fit(step, mesh, params, ustate, batch, key):
        fn = build_sharded_step(step, mesh, label="fit")
        params, ustate, score, skip = fn(params, ustate, batch, key, 0)
        fn2 = build_sharded_step(step, mesh, label="eval", donate=False)
        out = fn2(params, ustate, batch, key, 1)
        return params, out
    '''
    assert only(src, "donation-across-collective") == []


def test_donation_across_collective_resolves_local_factories():
    src = '''
    from deeplearning4j_tpu.compat import shard_map
    from deeplearning4j_tpu.runtime import compile_cache

    def make_round(body, mesh, specs):
        sharded = shard_map(body, mesh=mesh, in_specs=specs,
                            out_specs=specs)
        return compile_cache.cached_jit(sharded, label="round",
                                        donate_argnums=(0,))

    def drive(body, mesh, specs, state, batch):
        fn = make_round(body, mesh, specs)
        out = fn(state, batch)
        return state
    '''
    assert only(src, "donation-across-collective") == [14]


def test_donation_across_collective_suppression():
    src = '''
    from deeplearning4j_tpu.parallel.sharded_fit import build_sharded_step

    def fit(step, mesh, params, ustate, batch, key):
        fn = build_sharded_step(step, mesh, label="fit")
        new_p, new_u, score, skip = fn(params, ustate, batch, key, 0)
        return params  # jaxlint: disable=donation-across-collective — fixture
    '''
    assert only(src, "donation-across-collective") == []


# ---------------------------------------------------------------------------
# unlocked-shared-mutation
# ---------------------------------------------------------------------------

def test_unlocked_mutation_flags_public_side_without_lock():
    src = '''
    import threading

    class Batcher:
        def __init__(self):
            self._lock = threading.Lock()
            self._pending = []
            self._thread = threading.Thread(target=self._loop)

        def submit(self, x):
            self._pending.append(x)

        def _loop(self):
            with self._lock:
                self._pending.pop(0)
    '''
    assert only(src, "unlocked-shared-mutation") == [11]


def test_unlocked_mutation_common_lock_and_init_pass():
    src = '''
    import threading

    class Batcher:
        def __init__(self):
            self._cv = threading.Condition()
            self._pending = []          # pre-thread: exempt
            self._thread = threading.Thread(target=self._loop)

        def submit(self, x):
            with self._cv:
                self._pending.append(x)

        def close(self):
            with self._cv:
                self._open = False

        def _loop(self):
            with self._cv:
                self._pending.pop(0)
    '''
    assert only(src, "unlocked-shared-mutation") == []


def test_unlocked_mutation_resolves_targets_transitively():
    """Thread(target=self._run) where _run delegates via self._drain():
    the callee's mutations are worker-side too."""
    src = '''
    import threading

    class Runner:
        def __init__(self):
            self._lock = threading.Lock()
            self._items = []
            threading.Thread(target=self._run).start()

        def push(self, x):
            with self._lock:
                self._items.append(x)

        def _run(self):
            self._drain()

        def _drain(self):
            self._items.clear()
    '''
    assert only(src, "unlocked-shared-mutation") == [18]


def test_unlocked_mutation_sees_threads_built_in_comprehensions():
    """The DistributedRunner spelling: workers spawned in a list
    comprehension still resolve as thread targets."""
    src = '''
    import threading

    class Pool:
        def __init__(self, n):
            self._lock = threading.Lock()
            self._done = []
            self.workers = [threading.Thread(target=self._work)
                            for _ in range(n)]

        def collect(self):
            self._done.pop()

        def _work(self):
            with self._lock:
                self._done.append(1)
    '''
    assert only(src, "unlocked-shared-mutation") == [12]


def test_unlocked_mutation_lock_free_classes_are_out_of_scope():
    """No lock field to seed from => the class is lock-free by design
    (queues/events); the rule stays silent rather than guessing."""
    src = '''
    import threading

    class Flag:
        def __init__(self):
            self._stop = threading.Event()
            self._last = None
            threading.Thread(target=self._run).start()

        def update(self, x):
            self._last = x

        def _run(self):
            self._last = None
    '''
    assert only(src, "unlocked-shared-mutation") == []


def test_unlocked_mutation_suppression():
    src = '''
    import threading

    class Batcher:
        def __init__(self):
            self._lock = threading.Lock()
            self._hint = 0
            self._thread = threading.Thread(target=self._loop)

        def note(self, x):
            self._hint = x  # jaxlint: disable=unlocked-shared-mutation — monotonic hint, benign race

        def _loop(self):
            with self._lock:
                self._hint = 0
    '''
    assert only(src, "unlocked-shared-mutation") == []


# ---------------------------------------------------------------------------
# blocking-under-lock
# ---------------------------------------------------------------------------

def test_blocking_under_lock_flags_result_join_queue():
    src = '''
    import queue
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()
            self._thread = threading.Thread(target=self._run)

        def flush(self, fut):
            with self._lock:
                fut.result()

        def stop(self):
            with self._lock:
                self._thread.join()

        def pull(self):
            with self._lock:
                return self._q.get()

        def _run(self):
            pass
    '''
    assert only(src, "blocking-under-lock") == [13, 17, 21]


def test_blocking_under_lock_nonblocking_forms_pass():
    src = '''
    import queue
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition()
            self._q = queue.Queue()
            self._thread = threading.Thread(target=self._run)

        def pull(self):
            with self._lock:
                return self._q.get(block=False)

        def wait_ready(self):
            with self._cv:
                self._cv.wait()         # releases the held condition

        def outside(self, fut):
            with self._lock:
                x = 1
            fut.result()
            self._thread.join()

        def _run(self):
            pass
    '''
    assert only(src, "blocking-under-lock") == []


def test_blocking_under_lock_reentrant_lock_cases():
    src = '''
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._rlock = threading.RLock()
            self._thread = threading.Thread(target=self._run)

        def bad(self):
            with self._lock:
                with self._lock:
                    pass

        def fine(self):
            with self._rlock:
                with self._rlock:
                    pass

        def nested_distinct(self):
            with self._lock:
                with self._rlock:
                    pass

        def _run(self):
            pass
    '''
    assert only(src, "blocking-under-lock") == [12]


def test_blocking_under_lock_block_until_ready_and_sem():
    src = '''
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._sem = threading.BoundedSemaphore(2)
            self._thread = threading.Thread(target=self._run)

        def sync(self, out):
            with self._lock:
                out.block_until_ready()

        def reserve(self):
            with self._lock:
                self._sem.acquire()

        def _run(self):
            pass
    '''
    assert only(src, "blocking-under-lock") == [12, 16]


def test_blocking_under_lock_module_level_locks_count():
    src = '''
    import threading

    _LOCK = threading.Lock()

    def drain(t):
        t = threading.Thread(target=print)
        with _LOCK:
            t.join()
    '''
    assert only(src, "blocking-under-lock") == [9]


def test_blocking_under_lock_suppression():
    src = '''
    import queue
    import threading

    class Engine:
        def __init__(self):
            self._lock = threading.Lock()
            self._q = queue.Queue()
            self._thread = threading.Thread(target=self._run)

        def push(self, job):
            with self._lock:
                self._q.put(job)  # jaxlint: disable=blocking-under-lock — unbounded queue, never blocks

        def _run(self):
            pass
    '''
    assert only(src, "blocking-under-lock") == []


# ---------------------------------------------------------------------------
# impure-signal-handler
# ---------------------------------------------------------------------------

def test_signal_handler_flags_logging_metrics_locks():
    src = '''
    import signal
    import logging

    log = logging.getLogger(__name__)

    def on_term(signum, frame):
        log.warning("preempted")
        checkpoint_metrics.note("preemptions")
        print("bye")

    signal.signal(signal.SIGTERM, on_term)
    '''
    assert only(src, "impure-signal-handler") == [8, 9, 10]


def test_signal_handler_flag_only_body_passes():
    src = '''
    import signal
    import threading

    FLAG = threading.Event()

    def on_term(signum, frame):
        if FLAG.is_set():
            signal.signal(signum, signal.SIG_DFL)
            signal.raise_signal(signum)
            return
        FLAG.set()

    signal.signal(signal.SIGTERM, on_term)
    '''
    assert only(src, "impure-signal-handler") == []


def test_signal_handler_resolves_bound_method_registration():
    """The PreemptionGuard install form: signal.signal(s, self._handler)
    resolves to the class method, and the check follows self.* calls
    transitively."""
    src = '''
    import signal
    import threading

    class Guard:
        def __init__(self):
            self._requested = threading.Event()
            self._book_lock = threading.Lock()

        def _handler(self, signum, frame):
            self.request()

        def request(self):
            with self._book_lock:
                self._requested.set()

        def install(self):
            for s in (signal.SIGTERM, signal.SIGINT):
                signal.signal(s, self._handler)
    '''
    assert only(src, "impure-signal-handler") == [14]


def test_signal_handler_guard_subclass_hooks_are_handlers():
    """A PreemptionGuard subclass overriding request() is checked even
    with no visible signal.signal call — the base installs it."""
    src = '''
    from deeplearning4j_tpu.runtime.resilience import PreemptionGuard

    class ChattyGuard(PreemptionGuard):
        def request(self):
            telemetry.event("resilience.preempted")
    '''
    assert only(src, "impure-signal-handler") == [6]


def test_signal_handler_unresolvable_and_unregistered_pass():
    src = '''
    import logging

    log = logging.getLogger(__name__)

    def not_a_handler(signum, frame):
        log.warning("this function is never registered")
    '''
    assert only(src, "impure-signal-handler") == []


def test_signal_handler_suppression():
    src = '''
    import signal

    def on_term(signum, frame):
        print("bye")  # jaxlint: disable=impure-signal-handler — fixture

    signal.signal(signal.SIGTERM, on_term)
    '''
    assert only(src, "impure-signal-handler") == []


def test_repo_preemption_guard_handler_is_flag_only():
    """The PR 8 contract, machine-checked against the REAL source: the
    guard's handler chain carries no locks/logging/metrics."""
    src = (REPO_ROOT / "deeplearning4j_tpu" / "runtime"
           / "resilience.py").read_text()
    flagged = [f for f in check_source(
        src, "deeplearning4j_tpu/runtime/resilience.py")
        if f.rule == "impure-signal-handler"]
    assert flagged == []


# ---------------------------------------------------------------------------
# review-hardening regressions
# ---------------------------------------------------------------------------

def test_unlocked_mutation_resolves_timer_and_positional_targets():
    """Timer spells its callable ``function``/args[1] (args[0] is the
    interval), and Thread's args[0] is ``group`` — both positional
    forms must resolve (regression: args[0] was read for both)."""
    src = '''
    import threading

    class Flusher:
        def __init__(self):
            self._lock = threading.Lock()
            self._buf = []
            self._timer = threading.Timer(5.0, self._flush)

        def add(self, x):
            self._buf.append(x)

        def _flush(self):
            with self._lock:
                self._buf.clear()
    '''
    assert only(src, "unlocked-shared-mutation") == [11]
    src2 = src.replace("threading.Timer(5.0, self._flush)",
                       "threading.Timer(5.0, function=self._flush)")
    assert only(src2, "unlocked-shared-mutation") == [11]
    src3 = '''
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self._buf = []
            self._t = threading.Thread(None, self._flush)

        def add(self, x):
            self._buf.append(x)

        def _flush(self):
            with self._lock:
                self._buf.clear()
    '''
    assert only(src3, "unlocked-shared-mutation") == [11]


def test_unbound_axis_ignores_unrelated_scopes_and_resolves_for_loops():
    """A same-named string local to an UNRELATED function must not
    resolve another function's axis variable, and a literal for-loop
    binding over vocabulary axes is bound (regression: resolution
    walked every Assign in the module)."""
    src = '''
    from jax import lax

    def plot_helper():
        axis = "y"
        return axis

    def train_step(x):
        for axis in ("data", "model"):
            x = lax.psum(x, axis)
        return x
    '''
    assert only(src, "unbound-axis") == []
    # ...while a for-loop over a NON-vocabulary literal still flags
    src2 = '''
    from jax import lax

    def train_step(x):
        for axis in ("dta",):
            x = lax.psum(x, axis)
        return x
    '''
    assert only(src2, "unbound-axis") == [6]


def test_divergent_branch_static_counters_stay_clean():
    """A trace-static Python counter (``depth += 1``) must not taint —
    the branch is identical on every replica (regression: AugAssign
    tainted unconditionally)."""
    src = '''
    from jax import lax

    def train_step(params, grads):
        depth = 0
        depth += 1
        if depth % 2 == 0:
            grads = lax.psum(grads, "data")
        return grads
    '''
    assert only(src, "collective-in-divergent-branch") == []
    # ...but augmenting WITH a per-replica operand still taints
    src2 = '''
    from jax import lax

    def train_step(params, grads, loss):
        acc = 0.0
        acc += loss
        if acc > 1.0:
            grads = lax.psum(grads, "data")
        return grads
    '''
    assert only(src2, "collective-in-divergent-branch") == [8]


def test_refused_save_does_not_leak_in_flight_gauge():
    """AsyncCheckpointer.save() losing the race to close() after
    staging must bring the in-flight gauge back down (regression:
    note_staged's increment had no matching decrement on that path)."""
    import importlib.util
    spec = importlib.util.find_spec("jax")
    if spec is None:
        pytest.skip("jax unavailable")
    import numpy as np
    from deeplearning4j_tpu.runtime.checkpoint import (
        AsyncCheckpointer, CheckpointManager)
    from deeplearning4j_tpu.runtime.metrics import checkpoint_metrics
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        ck = AsyncCheckpointer(CheckpointManager(d))
        ck.save(0, {"w": np.ones((4,), np.float32)})
        ck.close(timeout=30)
        before = checkpoint_metrics.snapshot()["in_flight"]
        with pytest.raises(RuntimeError, match="closed"):
            ck.save(1, {"w": np.ones((4,), np.float32)})
        after = checkpoint_metrics.snapshot()["in_flight"]
        assert after == before


# ---------------------------------------------------------------------------
# PR 15: distributed-protocol family
# ---------------------------------------------------------------------------

def test_registry_ships_three_new_families():
    distributed = {"cluster-sync-in-divergent-branch",
                   "uncommitted-coordinator-write"}
    sharding = {"unknown-axis-in-partition-spec",
                "spec-without-divisibility-guard"}
    stability = {"unstable-cache-key", "host-sync-on-serving-worker"}
    assert distributed | sharding | stability <= set(REGISTRY)
    assert len(REGISTRY) >= 17
    for name in distributed:
        assert REGISTRY[name].family == "distributed-protocol"
    for name in sharding:
        assert REGISTRY[name].family == "sharding-layout"
    for name in stability:
        assert REGISTRY[name].family == "compile-stability"


def test_cli_list_rules_shows_new_families(capsys):
    assert jaxlint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for header in ("distributed-protocol:", "sharding-layout:",
                   "compile-stability:"):
        assert header in out
    for name in ("cluster-sync-in-divergent-branch",
                 "uncommitted-coordinator-write",
                 "unknown-axis-in-partition-spec",
                 "spec-without-divisibility-guard",
                 "unstable-cache-key", "host-sync-on-serving-worker"):
        assert name in out


def test_cluster_sync_flags_coordinator_gated_barrier():
    src = '''
    def save(cl, files):
        if cl.is_coordinator:
            cl.barrier("commit")
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == [4]


def test_cluster_sync_flags_divergent_early_return():
    """The divergent coordinator-only commit path the PR 14 review
    caught by hand: a non-coordinator early return makes every later
    statement coordinator-only."""
    src = '''
    def commit(cl, step):
        if not cl.is_coordinator:
            return
        cl.barrier("commit")
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == [5]


def test_cluster_sync_flags_except_handler_and_heartbeat_taint():
    src = '''
    def recover(cl, hb, path):
        try:
            write(path)
        except OSError:
            cl.barrier("retry")
        stale = hb.stale_members()
        if stale:
            cl.any_flag(True)
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == [6, 9]


def test_cluster_sync_flags_divergent_shrink_and_mutation_taint():
    """A receiver mutated with a divergent argument is tainted:
    ``lost.update(hb.lost_device_ids())`` forks the shrink."""
    src = '''
    def heal(cl, hb, err):
        lost = set(cl.agree_lost_ids(err.lost_ids))
        lost.update(hb.lost_device_ids())
        members = list(lost)
        if members:
            new = cl.shrink(members)
        return new
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == [7]


def test_cluster_sync_sanctioned_commit_shape_passes():
    """The runtime/checkpoint.py::_save_cluster shape: gather + gated
    WRITES + unconditional barriers, coordinator-only gc, and the
    non-coordinator manifest read — no finding from either
    distributed-protocol rule."""
    src = '''
    def _save_cluster(self, cl, step, tree, meta):
        mine = save_pytree(self._path(step), tree, meta)
        tables = cl.gather("crcs", "ckptcrc")
        files = (collect(tables) if cl.is_coordinator else {})
        cl.barrier("ckpt_data")
        if cl.is_coordinator:
            self._commit_manifest(step, files)
        cl.barrier("ckpt_commit")
        if cl.is_coordinator:
            self._gc()
        if not cl.is_coordinator:
            files = read_manifest(self._manifest_path(step))
        return files
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == []
    assert only(src, "uncommitted-coordinator-write") == []


def test_cluster_sync_post_agreement_decision_passes():
    """Branching on a value that FLOWED THROUGH a cluster primitive is
    the sanctioned pattern (the host-level post-psum rule)."""
    src = '''
    def drain(cl, flag):
        stop = cl.any_flag(flag)
        if stop:
            cl.barrier("drain")
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == []


def test_cluster_sync_suppression():
    src = '''
    def heal(cl, hb):
        stale = hb.stale_members()
        if stale:
            new = cl.shrink(stale)  # jaxlint: disable=cluster-sync-in-divergent-branch — fixture
        return new
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == []


def test_coordinator_write_flags_ungated_manifest_and_gc():
    src = '''
    def save(self, cl, step, files):
        cl.barrier("data")
        self._commit_manifest(step, files)
        self._gc()
    '''
    assert only(src, "uncommitted-coordinator-write") == [4, 5]


def test_coordinator_write_gated_forms_pass():
    """if-gate, not-coordinator early return, and the coordinator arm
    of a ternary all count as gated; a function with NO cluster
    rendezvous (the single-host save path) is out of scope."""
    src = '''
    def save_a(self, cl, step, files):
        cl.barrier("data")
        if cl.is_coordinator:
            self._commit_manifest(step, files)

    def save_b(self, cl, step, files):
        cl.barrier("data")
        if not cl.is_coordinator:
            return
        self._gc()

    def save_c(self, cl, step, files):
        cl.barrier("data")
        out = (self._commit_manifest(step, files)
               if cl.is_coordinator else None)
        return out

    def save_single(self, step, files):
        self._commit_manifest(step, files)
        self._gc()
    '''
    assert only(src, "uncommitted-coordinator-write") == []


def test_coordinator_write_suppression():
    src = '''
    def save(self, cl, step, files):
        cl.barrier("data")
        self._commit_manifest(step, files)  # jaxlint: disable=uncommitted-coordinator-write — fixture
    '''
    assert only(src, "uncommitted-coordinator-write") == []


# ---------------------------------------------------------------------------
# PR 15: sharding-layout family
# ---------------------------------------------------------------------------

MODELS_PATH = "deeplearning4j_tpu/models/fixture.py"


def test_partition_spec_flags_unknown_axis():
    src = '''
    from jax.sharding import PartitionSpec as P

    SPEC = P(None, "modle")
    OTHER = P(("data", "mdl"), None)
    '''
    assert only(src, "unknown-axis-in-partition-spec",
                path=MODELS_PATH) == [4, 5]


def test_partition_spec_resolves_constants_aliases_and_vocab():
    """Vocabulary literals, the mesh axis constants THROUGH the import,
    local aliases (incl. the IfExp idiom), and module-bound custom
    axes all pass; unresolvable entries stay silent."""
    src = '''
    from jax.sharding import Mesh, PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS, DATA_AXIS

    def specs(cfg, model_degree=1, axis=None):
        m = MODEL_AXIS if model_degree > 1 else None
        return {"w": P(None, m), "b": P(DATA_AXIS), "x": P("seq"),
                "caller": P(axis)}

    MESH = Mesh(devs, ("rows",))
    BOUND = P("rows", None)
    '''
    assert only(src, "unknown-axis-in-partition-spec",
                path=MODELS_PATH) == []


def test_partition_spec_scope_and_suppression():
    src = '''
    from jax.sharding import PartitionSpec as P
    SPEC = P("bogus")
    '''
    # out of the layout scope: nothing fires
    assert only(src, "unknown-axis-in-partition-spec",
                path="deeplearning4j_tpu/nn/fixture.py") == []
    sup = '''
    from jax.sharding import PartitionSpec as P
    SPEC = P("bogus")  # jaxlint: disable=unknown-axis-in-partition-spec — fixture
    '''
    assert only(sup, "unknown-axis-in-partition-spec",
                path=MODELS_PATH) == []


def test_divisibility_guard_flags_unguarded_model_factory():
    src = '''
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS

    def shard_specs(cfg, model_degree=1):
        return {"w1": P(None, MODEL_AXIS), "b1": P(MODEL_AXIS)}
    '''
    assert only(src, "spec-without-divisibility-guard",
                path=MODELS_PATH) == [5]


def test_divisibility_guard_modulo_and_delegation_pass():
    src = '''
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS
    from deeplearning4j_tpu.models import transformer as tfm

    def shard_specs(cfg, model_degree=1):
        if cfg.n_heads % model_degree:
            raise ValueError("n_heads not divisible")
        return {"w": P(None, MODEL_AXIS)}

    def other_specs(cfg, model_degree=1):
        specs = tfm.shard_specs(cfg, model_degree)
        specs["extra"] = P(MODEL_AXIS)
        return specs

    def data_specs(cfg):
        return {"x": P("data", None)}
    '''
    assert only(src, "spec-without-divisibility-guard",
                path=MODELS_PATH) == []


def test_divisibility_guard_def_line_suppression():
    src = '''
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS

    def paged_specs(cfg):  # jaxlint: disable=spec-without-divisibility-guard — engine validates at construction
        return {"k": P(None, MODEL_AXIS)}
    '''
    assert only(src, "spec-without-divisibility-guard",
                path=MODELS_PATH) == []


# ---------------------------------------------------------------------------
# PR 15: compile-stability family
# ---------------------------------------------------------------------------

def test_unstable_key_flags_planted_impurities():
    """The planted unstable-key fixture: id(), time.*, uuid, and the
    two f-string forms all defeat the zero-compile invariant."""
    src = '''
    import time
    import uuid
    from deeplearning4j_tpu.runtime import compile_cache

    def build(fn, params, ms):
        a = compile_cache.cached_jit(fn, key=("step", id(params)))
        b = compile_cache.get_or_build((time.time(), "x"), fn)
        c = compile_cache.cached_jit(fn, label=f"step[{params!r}]")
        d = compile_cache.cached_jit(fn, key=(uuid.uuid4(), "y"))
        e = compile_cache.cached_jit(fn, label=f"t{ms:.1f}")
        return a, b, c, d, e
    '''
    assert only(src, "unstable-cache-key") == [7, 8, 9, 10, 11]


def test_unstable_key_stable_forms_pass():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def build(fn, conf_json, mesh_sig, i):
        a = compile_cache.cached_jit(
            fn, key=("backprop", conf_json, mesh_sig),
            label=f"multilayer.gd[{i}]")
        b = compile_cache.get_or_build(("serving", conf_json), fn)
        return a, b
    '''
    assert only(src, "unstable-cache-key") == []


def test_unstable_key_suppression():
    src = '''
    from deeplearning4j_tpu.runtime import compile_cache

    def build(fn, params):
        return compile_cache.cached_jit(fn, key=("k", id(params)))  # jaxlint: disable=unstable-cache-key — fixture
    '''
    assert only(src, "unstable-cache-key") == []


SERVING_PATH = "deeplearning4j_tpu/serving/fixture.py"


def test_serving_worker_flags_syncs_in_worker_closure():
    src = '''
    import threading
    import numpy as np

    class Batcher:
        def __init__(self):
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            self._drain()

        def _drain(self):
            out = self._dispatch()
            toks = np.asarray(out)
            score = out.item()
            return toks, score
    '''
    assert only(src, "host-sync-on-serving-worker",
                path=SERVING_PATH) == [14, 15]


def test_serving_worker_cross_class_attribution_via_typed_attr():
    """The decode shape: the batcher worker drives the engine through a
    typed attribute, so the ENGINE method's fetch is attributed to the
    worker thread — and the two-arg np.asarray normalization idiom
    stays clean."""
    src = '''
    import threading
    import numpy as np

    class Engine:
        def advance(self):
            out = self._decode()
            return np.asarray(out)

        def start(self, prompt):
            prompt = np.asarray(prompt, np.int32)
            return self._prefill(prompt)

    class Batcher:
        def __init__(self, engine: Engine):
            self.engine = engine
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            self.engine.start([1])
            self.engine.advance()

        def submit(self, x):
            return np.asarray(x)
    '''
    # only Engine.advance's single-arg fetch fires: start's dtype
    # normalization and the CLIENT-side submit stay clean
    assert only(src, "host-sync-on-serving-worker",
                path=SERVING_PATH) == [8]


def test_serving_worker_local_thread_target_and_bare_reference():
    src = '''
    import threading
    import numpy as np
    import jax

    class Engine:
        def _ensure(self):
            q = self._q

            def loop():
                item = q.get()
                return np.asarray(item)

            threading.Thread(target=loop).start()

    class Batcher:
        def __init__(self):
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            out = self._dispatch()
            return jax.tree.map(np.asarray, out)
    '''
    assert only(src, "host-sync-on-serving-worker",
                path=SERVING_PATH) == [12, 22]


def test_serving_worker_scope_and_suppression():
    src = '''
    import threading
    import numpy as np

    class Batcher:
        def __init__(self):
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            return np.asarray(self._dispatch())
    '''
    # outside serving/: the rule does not apply
    assert only(src, "host-sync-on-serving-worker",
                path="deeplearning4j_tpu/nn/fixture.py") == []
    sup = '''
    import threading
    import numpy as np

    class Batcher:
        def __init__(self):
            self._thread = threading.Thread(target=self._loop)

        def _loop(self):
            return np.asarray(self._dispatch())  # jaxlint: disable=host-sync-on-serving-worker — fixture
    '''
    assert only(sup, "host-sync-on-serving-worker",
                path=SERVING_PATH) == []


def test_jaxlint_package_typechecks_under_mypy():
    """The linter that gates CI should not itself be type-unsound:
    mypy over tools/jaxlint with the committed zero-error config.
    Skips where mypy is not installed (the container gates it the same
    way in tools/ci.sh)."""
    if importlib.util.find_spec("mypy") is None:
        pytest.skip("mypy not installed")
    import subprocess
    proc = subprocess.run(
        [sys.executable, "-m", "mypy", "--config-file",
         str(REPO_ROOT / "tools" / "jaxlint" / "mypy.ini"),
         str(REPO_ROOT / "tools" / "jaxlint")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_ci_runs_the_typecheck_and_jobs_gates():
    """tools/ci.sh runs the grown analyzer with --jobs + --format json
    and the (gated) mypy pass over the analyzer package."""
    text = (REPO_ROOT / "tools" / "ci.sh").read_text()
    assert "--format json" in text
    assert "--jobs" in text
    assert "mypy" in text


# ---------------------------------------------------------------------------
# PR 15 review hardening regressions
# ---------------------------------------------------------------------------

def test_cluster_sync_branch_local_kill_keeps_taint():
    """A kill inside ONE conditional branch must not clear the taint
    for hosts that took the other path: branches scan taint copies and
    the parent keeps the union."""
    src = '''
    def f(cl, hb, cond):
        stale = hb.stale_members()
        if cond:
            stale = ()
        if stale:
            cl.barrier("x")
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == [7]


def test_cluster_sync_loop_local_break_is_not_an_early_exit():
    """A break absorbed by a loop nested INSIDE the divergent branch
    exits that loop, not the enclosing suite — the barrier after the
    branch is reached by every host."""
    src = '''
    def f(cl, hb, items):
        if hb.stale_members():
            for i in items:
                break
        cl.barrier("x")
    '''
    assert only(src, "cluster-sync-in-divergent-branch") == []


def test_coordinator_write_and_composed_negation_is_not_a_gate():
    """`if not cl.is_coordinator and fast: return` lets a
    non-coordinator with fast=False through — the write after it is
    NOT coordinator-only (only the True classification propagates
    through `and`)."""
    src = '''
    def save(self, cl, step, files, fast):
        cl.barrier("data")
        if not cl.is_coordinator and fast:
            return
        self._commit_manifest(step, files)
    '''
    assert only(src, "uncommitted-coordinator-write") == [6]


def test_partition_spec_param_shadows_module_binding():
    """A function parameter sharing a name with a module binding is
    the CALLER's value — statically unknowable, so it stays silent;
    the module-scope use of the same binding still resolves and
    flags."""
    src = '''
    from jax.sharding import PartitionSpec as P
    M = "modle"

    def f(M):
        return P(None, M)

    SPEC = P(None, M)
    '''
    assert only(src, "unknown-axis-in-partition-spec",
                path=MODELS_PATH) == [8]


# ---------------------------------------------------------------------------
# PR 17: blocking-in-health-monitor (serving watchdog contract)
# ---------------------------------------------------------------------------

def test_health_monitor_flags_untimed_blocking_and_device_syncs():
    """The watchdog contract: a monitor thread blocking unboundedly
    (or fetching device values) can be wedged by the very failure it
    exists to detect.  Attribution follows the thread NAME and closes
    over the monitor's same-class self-call graph (the replacement
    path runs on the monitor thread too)."""
    src = '''
    import threading
    import numpy as np

    class Router:
        def __init__(self):
            self._stop = threading.Event()
            self._monitor = threading.Thread(
                target=self._watch, name="dl4j-health-monitor")

        def _watch(self):
            while not self._stop.wait(0.25):
                self._replace()

        def _replace(self):
            self._cv.wait()
            self._drain.join()
            depth = self._depths.item()
            snap = np.asarray(self._depths)
    '''
    assert only(src, "blocking-in-health-monitor",
                path=SERVING_PATH) == [16, 17, 18, 19]


def test_health_monitor_timed_waits_and_host_reads_stay_clean():
    """The REAL monitor shape — timed Event.wait poll, host-side field
    reads, bounded joins — must not fire (the committed baseline stays
    empty)."""
    src = '''
    import threading

    class Router:
        def __init__(self):
            self._stop = threading.Event()
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="dl4j-health-monitor")

        def _monitor_loop(self):
            while not self._stop.wait(0.25):
                for b in list(self.batchers):
                    if not b.worker_alive():
                        self._replace(b)

        def _replace(self, b):
            b.close(timeout=5.0)
            self._drain.join(5.0)
    '''
    assert only(src, "blocking-in-health-monitor",
                path=SERVING_PATH) == []


def test_health_monitor_attribution_requires_monitor_name():
    """A worker thread that is NOT a health monitor is out of scope —
    the decode worker's untimed cv.wait is its designed park (other
    rules own worker discipline)."""
    src = '''
    import threading

    class Batcher:
        def __init__(self):
            self._thread = threading.Thread(
                target=self._loop, name="dl4j-decode-batcher")

        def _loop(self):
            self._cv.wait()
    '''
    assert only(src, "blocking-in-health-monitor",
                path=SERVING_PATH) == []


def test_health_monitor_scope_and_suppression():
    src = '''
    import threading

    class Router:
        def __init__(self):
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="m")

        def _monitor_loop(self):
            self._cv.wait()
    '''
    # method name carries the "monitor" attribution even when the
    # thread name does not
    assert only(src, "blocking-in-health-monitor",
                path=SERVING_PATH) == [10]
    # outside serving/: the rule does not apply
    assert only(src, "blocking-in-health-monitor",
                path="deeplearning4j_tpu/nn/fixture.py") == []
    sup = '''
    import threading

    class Router:
        def __init__(self):
            self._monitor = threading.Thread(
                target=self._monitor_loop, name="m")

        def _monitor_loop(self):
            self._cv.wait()  # jaxlint: disable=blocking-in-health-monitor — fixture
    '''
    assert only(sup, "blocking-in-health-monitor",
                path=SERVING_PATH) == []


def test_health_monitor_rule_registered_in_concurrency_family():
    assert REGISTRY["blocking-in-health-monitor"].family == "concurrency"


# ---------------------------------------------------------------------------
# PR 18: spec-axis-outside-mesh (4D mesh-shape contract)
# ---------------------------------------------------------------------------

def test_spec_axis_outside_mesh_flags_undeclared_axis():
    """A module that pins its mesh axes with a literal tuple must draw
    every resolvable spec axis from that tuple — 'pipe' is in the
    package vocabulary but not on THIS mesh, so only the stricter rule
    fires."""
    src = '''
    from jax.sharding import Mesh, PartitionSpec as P

    MESH = Mesh(devs, ("data", "model"))
    GOOD = P("data", "model")
    BAD = P(None, "pipe")
    '''
    assert only(src, "spec-axis-outside-mesh") == [6]
    assert only(src, "unknown-axis-in-partition-spec",
                path=MODELS_PATH) == []


def test_spec_axis_outside_mesh_resolves_axis_order_and_constants():
    """make_mesh's axis_order= kwarg declares the mesh too, through
    the exported axis constants; spec entries resolve through local
    aliases exactly like the vocabulary rule."""
    src = '''
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, MeshSpec, make_mesh)

    MESH = make_mesh(MeshSpec(data=2, model=2),
                     axis_order=(DATA_AXIS, MODEL_AXIS))

    def specs(model_degree=1):
        m = MODEL_AXIS if model_degree > 1 else None
        return {"w": P(None, m), "x": P(DATA_AXIS), "bad": P("expert")}
    '''
    assert only(src, "spec-axis-outside-mesh") == [11]


def test_spec_axis_outside_mesh_opaque_builder_stays_silent():
    """An unresolvable axis tuple (a parameter, a computed value)
    means the run-time axis set is unknowable — the rule must not
    guess.  parallel/mesh.py itself is this shape, which is why the
    shipped baseline stays empty."""
    src = '''
    from jax.sharding import Mesh, PartitionSpec as P

    def build(devs, axis_order):
        return Mesh(devs, axis_order)

    SPEC = P("pipe", "expert")
    '''
    assert only(src, "spec-axis-outside-mesh") == []


def test_spec_axis_outside_mesh_no_builder_out_of_scope():
    src = '''
    from jax.sharding import PartitionSpec as P
    SPEC = P("pipe")
    '''
    assert only(src, "spec-axis-outside-mesh") == []


def test_spec_axis_outside_mesh_suppression_and_registry():
    sup = '''
    from jax.sharding import Mesh, PartitionSpec as P
    MESH = Mesh(devs, ("data",))
    SPEC = P("model")  # jaxlint: disable=spec-axis-outside-mesh — fixture
    '''
    assert only(sup, "spec-axis-outside-mesh") == []
    assert REGISTRY["spec-axis-outside-mesh"].family == "sharding-layout"


# ---------------------------------------------------------------------------
# PR 19: two-pass linked analysis — summaries, linking, cross-module rules
# ---------------------------------------------------------------------------

from tools.jaxlint.link import check_linked_sources, link_sources  # noqa: E402


def linked_only(srcs, rule):
    """(path, line) pairs at which ``rule`` fired across a linked
    in-memory fixture tree."""
    out = []
    for path, findings in sorted(check_linked_sources(srcs).items()):
        out.extend((path, f.line) for f in findings if f.rule == rule)
    return out


_ALLOCATOR_MOD = '''\
class KVPagesExhausted(RuntimeError):
    pass

class PageAllocator:
    def alloc(self, n):
        return list(range(n))
    def share(self, pids):
        return pids
    def free(self, pids):
        pass
'''


def test_registry_ships_cross_module_family():
    cross = {"cross-module-use-after-donate", "cross-module-spec-mesh",
             "page-refcount-balance", "unstable-imported-cache-key"}
    assert cross <= set(REGISTRY)
    assert len(REGISTRY) >= 21
    for name in cross:
        assert REGISTRY[name].family == "cross-module"
        assert REGISTRY[name].requires_link
    # and no other rule requires linking
    for name, rule in REGISTRY.items():
        if name not in cross:
            assert not rule.requires_link


def test_cross_module_rules_skipped_without_link_context():
    """A single-module check_source call (no LinkContext) must not
    half-run a linking rule — it is skipped entirely."""
    src = '''
    from pkg.dep import train
    def go(params, batch):
        out = train(params, batch)
        print(params)
    '''
    assert fired(src, path="pkg/use.py") == []


# -- cross-module-use-after-donate ------------------------------------------

_DONATING_DEP = '''\
from runtime.compile_cache import cached_jit

def train(params, batch):
    step = cached_jit(_body, donate_argnums=(0,))
    return step(params, batch)
'''


def test_cross_module_donate_flags_read_after_call():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/dep.py": _DONATING_DEP,
        "pkg/use.py": ("from pkg.dep import train\n"
                       "def go(params, batch):\n"
                       "    out = train(params, batch)\n"
                       "    print(params)\n"
                       "    return out\n"),
    }
    assert linked_only(srcs, "cross-module-use-after-donate") \
        == [("pkg/use.py", 4)]
    # the message carries the summary provenance: module + position
    (f,) = check_linked_sources(srcs)["pkg/use.py"]
    assert "pkg.dep" in f.message and "donates positional arg" in f.message


def test_cross_module_donate_rebind_from_result_is_clean():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/dep.py": _DONATING_DEP,
        "pkg/use.py": ("from pkg.dep import train\n"
                       "def go(params, batch):\n"
                       "    params = train(params, batch)\n"
                       "    return params\n"),
    }
    assert linked_only(srcs, "cross-module-use-after-donate") == []


def test_cross_module_donate_forwarding_chain_links():
    """A re-export wrapper donates too: the linker closes donation over
    forwarding chains, so the fact crosses TWO module boundaries."""
    srcs = {
        "pkg/__init__.py": "",
        "pkg/dep.py": _DONATING_DEP,
        "pkg/wrap.py": ("from pkg.dep import train\n"
                        "def fit(params, batch):\n"
                        "    return train(params, batch)\n"),
        "pkg/use.py": ("from pkg.wrap import fit\n"
                       "def go(params, batch):\n"
                       "    out = fit(params, batch)\n"
                       "    print(params)\n"),
    }
    assert linked_only(srcs, "cross-module-use-after-donate") \
        == [("pkg/use.py", 4)]


def test_cross_module_donate_suppression():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/dep.py": _DONATING_DEP,
        "pkg/use.py": (
            "from pkg.dep import train\n"
            "def go(params, batch):\n"
            "    out = train(params, batch)\n"
            "    print(params)  # jaxlint: disable=cross-module-use-after-donate — fixture\n"),
    }
    assert linked_only(srcs, "cross-module-use-after-donate") == []


# -- cross-module-spec-mesh -------------------------------------------------

_SPEC_FACTORY = '''\
from jax.sharding import PartitionSpec as P

def shard_specs(conf):
    return {"w": P("model", None), "b": P(None)}
'''


def test_cross_module_spec_mesh_flags_undeclared_axis():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/gpt.py": _SPEC_FACTORY,
        "pkg/driver.py": ("from jax.sharding import Mesh\n"
                          "from pkg.gpt import shard_specs\n"
                          "def run(devs, conf):\n"
                          "    mesh = Mesh(devs, ('data',))\n"
                          "    return mesh, shard_specs(conf)\n"),
    }
    assert linked_only(srcs, "cross-module-spec-mesh") \
        == [("pkg/driver.py", 5)]
    (f,) = check_linked_sources(srcs)["pkg/driver.py"]
    assert "pkg.gpt" in f.message and "'model'" in f.message


def test_cross_module_spec_mesh_declared_axis_is_clean():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/gpt.py": _SPEC_FACTORY,
        "pkg/driver.py": ("from jax.sharding import Mesh\n"
                          "from pkg.gpt import shard_specs\n"
                          "def run(devs, conf):\n"
                          "    mesh = Mesh(devs, ('data', 'model'))\n"
                          "    return mesh, shard_specs(conf)\n"),
    }
    assert linked_only(srcs, "cross-module-spec-mesh") == []


def test_cross_module_spec_mesh_abstains_without_local_mesh():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/gpt.py": _SPEC_FACTORY,
        "pkg/driver.py": ("from pkg.gpt import shard_specs\n"
                          "def run(conf):\n"
                          "    return shard_specs(conf)\n"),
    }
    assert linked_only(srcs, "cross-module-spec-mesh") == []


def test_cross_module_spec_mesh_abstains_on_opaque_mesh_or_specs():
    # opaque mesh tuple: run-time axes unknowable
    srcs = {
        "pkg/__init__.py": "",
        "pkg/gpt.py": _SPEC_FACTORY,
        "pkg/driver.py": ("from jax.sharding import Mesh\n"
                          "from pkg.gpt import shard_specs\n"
                          "def run(devs, conf, axis_order):\n"
                          "    mesh = Mesh(devs, axis_order)\n"
                          "    return mesh, shard_specs(conf)\n"),
    }
    assert linked_only(srcs, "cross-module-spec-mesh") == []
    # opaque factory (spec entry not resolvable): summary abstains
    srcs["pkg/gpt.py"] = (
        "from jax.sharding import PartitionSpec as P\n"
        "def shard_specs(conf, ax):\n"
        "    return {'w': P(ax)}\n")
    srcs["pkg/driver.py"] = (
        "from jax.sharding import Mesh\n"
        "from pkg.gpt import shard_specs\n"
        "def run(devs, conf):\n"
        "    mesh = Mesh(devs, ('data',))\n"
        "    return mesh, shard_specs(conf, 'model')\n")
    assert linked_only(srcs, "cross-module-spec-mesh") == []


def test_cross_module_spec_mesh_suppression():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/gpt.py": _SPEC_FACTORY,
        "pkg/driver.py": (
            "from jax.sharding import Mesh\n"
            "from pkg.gpt import shard_specs\n"
            "def run(devs, conf):\n"
            "    mesh = Mesh(devs, ('data',))\n"
            "    return mesh, shard_specs(conf)  # jaxlint: disable=cross-module-spec-mesh — host-only specs\n"),
    }
    assert linked_only(srcs, "cross-module-spec-mesh") == []


# -- page-refcount-balance --------------------------------------------------

def test_page_refcount_pr17_reconstruction_flags_handler_raise():
    """The shipped incident, as a fixture: pages alloc'd BEFORE a try,
    freed only in the try body, re-raised from the handler — the
    exception path leaks the pages (this is the leak the PR 17 finally
    fixed)."""
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/admit.py": (
            "from pkg.alloc import PageAllocator, KVPagesExhausted\n"
            "def admit(pool: PageAllocator, req):\n"
            "    pages = pool.alloc(req.n)\n"
            "    try:\n"
            "        dispatch(req, pages)\n"
            "        pool.free(pages)\n"
            "    except KVPagesExhausted:\n"
            "        raise\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") \
        == [("pkg/admit.py", 8)]
    (f,) = check_linked_sources(srcs)["pkg/admit.py"]
    assert "raise" in f.message and "pkg.alloc" in f.message


def test_page_refcount_finally_fix_is_clean():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/admit.py": (
            "from pkg.alloc import PageAllocator\n"
            "def admit(pool: PageAllocator, req):\n"
            "    pages = pool.alloc(req.n)\n"
            "    try:\n"
            "        dispatch(req, pages)\n"
            "    finally:\n"
            "        pool.free(pages)\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") == []


def test_page_refcount_handler_that_frees_before_reraise_is_clean():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/admit.py": (
            "from pkg.alloc import PageAllocator, KVPagesExhausted\n"
            "def admit(pool: PageAllocator, req):\n"
            "    pages = pool.alloc(req.n)\n"
            "    try:\n"
            "        dispatch(req, pages)\n"
            "        pool.free(pages)\n"
            "    except KVPagesExhausted:\n"
            "        pool.free(pages)\n"
            "        raise\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") == []


def test_page_refcount_call_argument_is_not_a_transfer():
    """dispatch(pages) then falling off the end IS the leak shape —
    passing the name as a call argument transfers nothing."""
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/go.py": ("from pkg.alloc import PageAllocator\n"
                      "def go(pool: PageAllocator, n):\n"
                      "    pages = pool.alloc(n)\n"
                      "    dispatch(pages)\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") \
        == [("pkg/go.py", 3)]


def test_page_refcount_ownership_transfers_are_silent():
    base = {"pkg/__init__.py": "", "pkg/alloc.py": _ALLOCATOR_MOD}
    for body in (
            "    return pages\n",                 # returned
            "    slot.pages = pages\n",           # stored into an attr
            "    table[k] = pages\n",             # stored into a subscript
            "    queue.append(pages)\n"):         # handed to a container
        srcs = dict(base)
        srcs["pkg/go.py"] = ("from pkg.alloc import PageAllocator\n"
                             "def go(pool: PageAllocator, n, slot, table,"
                             " queue, k):\n"
                             "    pages = pool.alloc(n)\n" + body)
        assert linked_only(srcs, "page-refcount-balance") == [], body


def test_page_refcount_discard_and_share_and_conditional_free():
    base = {"pkg/__init__.py": "", "pkg/alloc.py": _ALLOCATOR_MOD}
    # result discarded on the spot
    srcs = dict(base)
    srcs["pkg/go.py"] = ("from pkg.alloc import PageAllocator\n"
                         "def go(pool: PageAllocator, n):\n"
                         "    pool.alloc(n)\n")
    assert linked_only(srcs, "page-refcount-balance") \
        == [("pkg/go.py", 3)]
    # share takes a reference too — receiver typed via constructor
    srcs = dict(base)
    srcs["pkg/go.py"] = ("from pkg.alloc import PageAllocator\n"
                         "def go(pages):\n"
                         "    pool = PageAllocator()\n"
                         "    pool.share(pages)\n"
                         "    broadcast(pages)\n")
    assert linked_only(srcs, "page-refcount-balance") \
        == [("pkg/go.py", 4)]
    # released only inside a branch: the normal path leaks
    srcs = dict(base)
    srcs["pkg/go.py"] = ("from pkg.alloc import PageAllocator\n"
                         "def go(pool: PageAllocator, n, cond):\n"
                         "    pages = pool.alloc(n)\n"
                         "    if cond:\n"
                         "        pool.free(pages)\n")
    assert linked_only(srcs, "page-refcount-balance") \
        == [("pkg/go.py", 3)]


def test_page_refcount_abstains_when_acquire_inside_try_body():
    """An except handler of the try whose BODY holds the alloc may run
    with the alloc never having happened (the alloc itself raised) —
    the rule cannot prove a leak there (decode.py's prefill shape)."""
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/go.py": ("from pkg.alloc import PageAllocator\n"
                      "def go(pool: PageAllocator, b, slot, n):\n"
                      "    try:\n"
                      "        fresh = pool.alloc(n)\n"
                      "    except RuntimeError:\n"
                      "        raise\n"
                      "    b.ptab[slot] = fresh\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") == []


def test_page_refcount_self_attr_receiver_and_early_return():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/engine.py": (
            "from pkg.alloc import PageAllocator\n"
            "class Engine:\n"
            "    def __init__(self):\n"
            "        self._pool = PageAllocator()\n"
            "    def step(self, n, cond):\n"
            "        pages = self._pool.alloc(n)\n"
            "        if cond:\n"
            "            return None\n"
            "        run(pages)\n"
            "        self._pool.free(pages)\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") \
        == [("pkg/engine.py", 8)]


def test_page_refcount_suppression():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/alloc.py": _ALLOCATOR_MOD,
        "pkg/go.py": (
            "from pkg.alloc import PageAllocator\n"
            "def go(pool: PageAllocator, n):\n"
            "    pages = pool.alloc(n)  # jaxlint: disable=page-refcount-balance — freed by callee\n"
            "    dispatch(pages)\n"),
    }
    assert linked_only(srcs, "page-refcount-balance") == []


# -- unstable-imported-cache-key --------------------------------------------

_KEY_HELPERS = '''\
import time
import json

def run_tag():
    return f"run-{time.time()}"

def conf_key(conf):
    return json.dumps(conf, sort_keys=True)
'''


def test_unstable_imported_cache_key_flags_and_carries_reason():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/keys.py": _KEY_HELPERS,
        "pkg/use.py": (
            "from runtime.compile_cache import cached_jit\n"
            "from pkg.keys import run_tag\n"
            "def build(step):\n"
            "    return cached_jit(step, key=run_tag())\n"),
    }
    assert linked_only(srcs, "unstable-imported-cache-key") \
        == [("pkg/use.py", 4)]
    (f,) = check_linked_sources(srcs)["pkg/use.py"]
    assert "pkg.keys" in f.message and "time.time()" in f.message


def test_unstable_imported_cache_key_transitive_provenance():
    """Impurity two modules deep still reaches the call site, and the
    reason names the chain."""
    srcs = {
        "pkg/__init__.py": "",
        "pkg/keys.py": _KEY_HELPERS,
        "pkg/mid.py": ("from pkg.keys import run_tag\n"
                       "def wrapper():\n"
                       "    return run_tag()\n"),
        "pkg/use.py": (
            "from runtime.compile_cache import cached_jit\n"
            "from pkg.mid import wrapper\n"
            "def build(step):\n"
            "    return cached_jit(step, key=wrapper())\n"),
    }
    assert linked_only(srcs, "unstable-imported-cache-key") \
        == [("pkg/use.py", 4)]
    (f,) = check_linked_sources(srcs)["pkg/use.py"]
    assert "wrapper" in f.message and "run_tag" in f.message


def test_unstable_imported_cache_key_pure_helper_is_clean():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/keys.py": _KEY_HELPERS,
        "pkg/use.py": (
            "from runtime.compile_cache import cached_jit\n"
            "from pkg.keys import conf_key\n"
            "def build(step, conf):\n"
            "    return cached_jit(step, key=conf_key(conf))\n"),
    }
    assert linked_only(srcs, "unstable-imported-cache-key") == []


def test_unstable_imported_cache_key_suppression():
    srcs = {
        "pkg/__init__.py": "",
        "pkg/keys.py": _KEY_HELPERS,
        "pkg/use.py": (
            "from runtime.compile_cache import cached_jit\n"
            "from pkg.keys import run_tag\n"
            "def build(step):\n"
            "    return cached_jit(step, key=run_tag())  # jaxlint: disable=unstable-imported-cache-key — bench harness\n"),
    }
    assert linked_only(srcs, "unstable-imported-cache-key") == []


# -- linking mechanics ------------------------------------------------------

def test_import_cycle_summaries_converge():
    """Mutually importing modules must link by fixpoint, not recursion:
    donation and purity facts settle, and no RecursionError escapes."""
    srcs = {
        "pkg/__init__.py": "",
        "pkg/a.py": ("from runtime.compile_cache import cached_jit\n"
                     "from pkg.b import pong\n"
                     "def ping(params, batch):\n"
                     "    step = cached_jit(_body, donate_argnums=(0,))\n"
                     "    return step(params, batch)\n"
                     "def akey():\n"
                     "    return pong()\n"),
        "pkg/b.py": ("import time\n"
                     "from pkg.a import ping\n"
                     "def fit(params, batch):\n"
                     "    return ping(params, batch)\n"
                     "def pong():\n"
                     "    return time.time()\n"),
    }
    ctxs = link_sources(srcs)
    (_tree, ctx) = ctxs["pkg/a.py"]
    # donation flowed a -> b through the cycle
    assert ctx.function_summary("pkg.b", "fit")["donates_linked"] == [0]
    # impurity flowed b -> a through the cycle, with provenance
    akey = ctx.function_summary("pkg.a", "akey")
    assert akey["key_pure"] is False
    assert "pong" in akey["key_impure_reason"]


# -- summary cache + dependency-aware result cache --------------------------

_DEP_DONATING = '''\
from runtime.compile_cache import cached_jit

def train(params, batch):
    step = cached_jit(_body, donate_argnums=(0,))
    return step(params, batch)
'''

_DEP_PLAIN = '''\
def train(params, batch):
    return _body(params, batch)
'''

_USE_SRC = '''\
from pkg.dep import train

def go(params, batch):
    out = train(params, batch)
    print(params)
    return out
'''


def _linked_pkg(tmp_path, dep_src=_DEP_DONATING):
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "dep.py").write_text(dep_src)
    (pkg / "use.py").write_text(_USE_SRC)
    return pkg


def test_warm_run_reextracts_zero_summaries(tmp_path):
    """The acceptance criterion: a warm re-run with nothing changed
    re-extracts NO summaries — every one is served from the store."""
    pkg = _linked_pkg(tmp_path)
    cache = tmp_path / "cache.json"
    stats: dict = {}
    run_paths([pkg], cache_path=cache, stats=stats)
    assert stats["summaries_extracted"] >= 3  # pkg + dep + use
    assert stats["summaries_cached"] == 0
    stats2: dict = {}
    findings = run_paths([pkg], cache_path=cache, stats=stats2)
    assert stats2["summaries_extracted"] == 0
    assert stats2["summaries_cached"] == stats["summaries_extracted"]
    assert [f.rule for f in findings] == ["cross-module-use-after-donate"]


def test_dependency_edit_relinks_importer(tmp_path):
    """The v4 staleness fix: editing dep.py's CONTRACT must re-lint
    use.py even though use.py's own text (and cache key) is unchanged."""
    pkg = _linked_pkg(tmp_path)
    cache = tmp_path / "cache.json"
    f1 = run_paths([pkg], cache_path=cache)
    assert [f.rule for f in f1] == ["cross-module-use-after-donate"]
    # dependency stops donating: the importer's finding must vanish
    (pkg / "dep.py").write_text(_DEP_PLAIN)
    stats: dict = {}
    f2 = run_paths([pkg], cache_path=cache, stats=stats)
    assert f2 == []
    assert stats["summaries_extracted"] == 1  # only dep re-extracted
    # and back: the finding returns (nothing stale in either direction)
    (pkg / "dep.py").write_text(_DEP_DONATING)
    f3 = run_paths([pkg], cache_path=cache)
    assert [f.rule for f in f3] == ["cross-module-use-after-donate"]


def test_docstring_only_dep_edit_keeps_importer_cached(tmp_path):
    """Summary fingerprints are content hashes of the SUMMARY, not the
    source: a docstring edit in dep.py re-extracts dep's summary but
    must not re-lint use.py.  Proven by poisoning use.py's cache entry
    — the poison is served only if the cache hit."""
    pkg = _linked_pkg(tmp_path)
    cache = tmp_path / "cache.json"
    run_paths([pkg], cache_path=cache)
    data = json.loads(cache.read_text())
    use_key = next(k for k in data if k.endswith("use.py"))
    data[use_key]["findings"] = []          # poison
    cache.write_text(json.dumps(data))
    (pkg / "dep.py").write_text('"""docs only."""\n' + _DEP_DONATING)
    f = run_paths([pkg], cache_path=cache)
    assert f == []                          # poison served: cache hit
    # whereas a contract edit busts it (the poison is NOT served)
    data = json.loads(cache.read_text())
    data[use_key]["findings"] = []
    cache.write_text(json.dumps(data))
    (pkg / "dep.py").write_text(_DEP_PLAIN + "\ndef extra():\n    pass\n")
    (pkg / "dep.py").write_text(_DEP_DONATING.replace(
        "donate_argnums=(0,)", "donate_argnums=(0, 1)"))
    f = run_paths([pkg], cache_path=cache)
    assert [x.rule for x in f] == ["cross-module-use-after-donate"]


def test_module_rename_invalidates_importer(tmp_path):
    """Renaming dep.py changes use.py's resolvable dependency set, so
    its cached (linked) result must not be served."""
    pkg = _linked_pkg(tmp_path)
    cache = tmp_path / "cache.json"
    f1 = run_paths([pkg], cache_path=cache)
    assert [f.rule for f in f1] == ["cross-module-use-after-donate"]
    data = json.loads(cache.read_text())
    use_key = next(k for k in data if k.endswith("use.py"))
    bogus = dict(data[use_key]["findings"][0])
    bogus["message"] = "stale-poison"
    data[use_key]["findings"] = [bogus]
    cache.write_text(json.dumps(data))
    (pkg / "dep.py").rename(pkg / "helper.py")
    f2 = run_paths([pkg], cache_path=cache)
    # the import no longer resolves: no summary, no cross-module
    # finding — and the poisoned stale entry was NOT served
    assert not any(x.message == "stale-poison" for x in f2)
    assert [x.rule for x in f2] == []


def test_schema_bump_discards_store_and_reextracts(tmp_path, monkeypatch):
    """A summary-schema version bump must re-extract EVERYTHING — the
    store is discarded whole, never half-read."""
    from tools.jaxlint import summary as summary_mod

    pkg = _linked_pkg(tmp_path)
    cache = tmp_path / "cache.json"
    stats: dict = {}
    run_paths([pkg], cache_path=cache, stats=stats)
    total = stats["summaries_extracted"]
    monkeypatch.setattr(summary_mod, "SCHEMA_VERSION",
                        summary_mod.SCHEMA_VERSION + 1)
    stats2: dict = {}
    run_paths([pkg], cache_path=cache, stats=stats2)
    assert stats2["summaries_extracted"] == total
    assert stats2["summaries_cached"] == 0
    # warm again under the NEW schema: fully cached once more
    stats3: dict = {}
    run_paths([pkg], cache_path=cache, stats=stats3)
    assert stats3["summaries_extracted"] == 0


def test_linked_jobs_output_is_deterministic(tmp_path, capsys):
    """--jobs N determinism holds for the linked pipeline too: the
    summary table is read-only during pass 2, results stitch back in
    file order (ISSUE 19 satellite #3)."""
    pkg = _linked_pkg(tmp_path)
    for i in range(4):
        (pkg / f"use{i}.py").write_text(_USE_SRC)
    outs = []
    for jobs in ("1", "4"):
        assert jaxlint_main([str(pkg), "--no-baseline",
                             "--jobs", jobs]) == 1
        outs.append(capsys.readouterr().out)
    assert outs[0] == outs[1]
    assert outs[0].count("cross-module-use-after-donate") == 5


# -- CLI: --dump-summaries, --no-link, json timings, baseline ---------------

def test_cli_dump_summaries_module(tmp_path, capsys):
    pkg = _linked_pkg(tmp_path)
    assert jaxlint_main(["--dump-summaries=pkg.dep", str(pkg)]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["module"] == "pkg.dep"
    assert data["functions"]["train"]["donates_linked"] == [0]


def test_cli_dump_summaries_all_and_unknown_module(tmp_path, capsys):
    pkg = _linked_pkg(tmp_path)
    # flag LAST: the nargs="?" form would swallow a following path as
    # the module name (the help text says --dump-summaries=MODULE)
    assert jaxlint_main([str(pkg), "--dump-summaries"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert {"pkg", "pkg.dep", "pkg.use"} <= set(data)
    assert jaxlint_main(["--dump-summaries=no.such.mod", str(pkg)]) == 2
    assert "no export summary" in capsys.readouterr().err


def test_cli_format_json_reports_pass_timings(tmp_path, capsys):
    pkg = _linked_pkg(tmp_path)
    assert jaxlint_main([str(pkg), "--no-baseline",
                         "--format", "json"]) == 1
    data = json.loads(capsys.readouterr().out)
    assert data["summary_ms"] >= 0.0 and data["link_ms"] >= 0.0
    assert data["summaries_extracted"] >= 3
    (rec,) = [r for r in data["findings"]
              if r["rule"] == "cross-module-use-after-donate"]
    assert rec["family"] == "cross-module"


def test_cli_no_link_skips_cross_module_rules(tmp_path, capsys):
    pkg = _linked_pkg(tmp_path)
    assert jaxlint_main([str(pkg), "--no-baseline", "--no-link",
                         "--format", "json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["findings"] == []
    assert data["summaries_extracted"] == 0


def test_write_baseline_round_trips_cross_module_findings(tmp_path,
                                                          capsys):
    """A cross-module finding baselines like any other: location is the
    CALL SITE (consumer file), and a subsequent run is clean against
    the written baseline (ISSUE 19 satellite #5)."""
    pkg = _linked_pkg(tmp_path)
    bl = tmp_path / "bl.json"
    assert jaxlint_main([str(pkg), "--baseline", str(bl),
                         "--write-baseline"]) == 0
    capsys.readouterr()
    entries = json.loads(bl.read_text())["entries"]
    (entry,) = [e for e in entries
                if e["rule"] == "cross-module-use-after-donate"]
    assert entry["path"].endswith("use.py")     # call site, not callee
    assert jaxlint_main([str(pkg), "--baseline", str(bl)]) == 0


# -- docs drift guard -------------------------------------------------------

def test_readme_rule_table_matches_registry():
    """The README 'Static analysis' rule tables must name EXACTLY the
    registered rule set — a new rule without docs (or a renamed rule
    with stale docs) fails here (ISSUE 19 satellite #4)."""
    text = (REPO_ROOT / "README.md").read_text(encoding="utf-8")
    start = text.index("## Static analysis")
    end = text.index("\n## ", start + 1)
    documented = set()
    for line in text[start:end].splitlines():
        stripped = line.strip()
        if stripped.startswith("| `") and "` |" in stripped:
            documented.add(stripped[3:stripped.index("`", 3)])
    assert documented == set(REGISTRY), (
        f"README-only: {sorted(documented - set(REGISTRY))}; "
        f"undocumented: {sorted(set(REGISTRY) - documented)}")
