"""repro.analysis: every rule fires on a known-bad fixture, stays quiet on
the idiomatic good pattern, and the suppression/baseline machinery
round-trips.

The two seeded regression checks pin the linter against bugs this repo
actually shipped: PR 7's ``time.time()`` wall-clock reads in the launch
plane (DET001) and PR 5's ``functools.cache`` on the backend probe
(JIT001). If a refactor ever weakens those rules, these tests fail before
the bug can come back.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import analyze_repo, load_baseline, write_baseline
from repro.analysis.cli import main as cli_main

REPO_ROOT = Path(__file__).resolve().parent.parent


def run(tmp_path, files):
    """Materialize ``{relpath: source}`` under a scratch repo root and lint
    it (no baseline unless the caller wrote one)."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return analyze_repo(root=tmp_path)


def rules(result):
    return [f.rule for f in result.findings]


# ---------------------------------------------------------------------------
# DET001 — wall clock (the PR 7 regression)
# ---------------------------------------------------------------------------

def test_det001_catches_pr7_wall_clock_pattern(tmp_path):
    """Seeded regression: the exact ``t0 = time.time()`` timing pattern that
    PR 7 had to scrub out of the fault/launch planes must fire DET001."""
    r = run(tmp_path, {"src/repro/launch/serve.py": """
        import time

        def generate(cfg):
            t0 = time.time()
            out = compile_it(cfg)
            return out, time.time() - t0
    """})
    assert rules(r) == ["DET001", "DET001"]
    assert "inject a clock" in r.findings[0].message


def test_det001_quiet_on_injectable_clock_default(tmp_path):
    """Referencing ``time.perf_counter`` as the injectable *default* is the
    sanctioned pattern (runtime/fault.py) — only direct calls are flagged."""
    r = run(tmp_path, {"src/repro/launch/serve.py": """
        import time

        def generate(cfg, clock=None):
            clock = clock or time.perf_counter
            t0 = clock()
            return clock() - t0
    """})
    assert rules(r) == []


def test_det001_ignores_non_deterministic_dirs(tmp_path):
    r = run(tmp_path, {"src/repro/utils/profiling.py": """
        import time

        def stamp():
            return time.time()
    """})
    assert rules(r) == []


# ---------------------------------------------------------------------------
# DET002 / DET003 — RNG discipline
# ---------------------------------------------------------------------------

def test_det002_catches_global_rng(tmp_path):
    r = run(tmp_path, {"src/repro/sim/noise.py": """
        import random
        import numpy as np

        def draw(n):
            np.random.seed(0)
            return np.random.rand(n) + random.random()
    """})
    assert sorted(rules(r)) == ["DET002", "DET002", "DET002"]


def test_det003_requires_domain_tagged_tuple_seed(tmp_path):
    r = run(tmp_path, {"src/repro/core/place.py": """
        import numpy as np

        def a(seed):
            return np.random.default_rng(seed)        # scalar: shared stream

        def b():
            return np.random.default_rng()            # OS entropy

        def c(seed):
            return np.random.default_rng((seed, 0xFA17))   # idiomatic
    """})
    assert rules(r) == ["DET003", "DET003"]
    assert {f.scope for f in r.findings} == {"a", "b"}


# ---------------------------------------------------------------------------
# JIT001 — cached state (the PR 5 regression)
# ---------------------------------------------------------------------------

def test_jit001_catches_pr5_cached_backend_probe(tmp_path):
    """Seeded regression: PR 5's bug verbatim — ``functools.cache`` on the
    interpret-mode probe froze ``jax.default_backend()``'s first answer for
    the life of the process."""
    r = run(tmp_path, {"src/repro/kernels/probe.py": """
        import functools
        import jax

        @functools.cache
        def _default_interpret():
            return jax.default_backend() != "tpu"
    """})
    assert rules(r) == ["JIT001"]
    assert "jax.default_backend" in r.findings[0].message


def test_jit001_flags_lru_cache_over_mutable_registry(tmp_path):
    r = run(tmp_path, {"src/repro/core/reg.py": """
        import functools

        _REGISTRY = {}

        @functools.lru_cache(maxsize=None)
        def lookup(name):
            return _REGISTRY[name]
    """})
    assert rules(r) == ["JIT001"]


def test_jit001_quiet_on_pure_cache_and_uncached_probe(tmp_path):
    r = run(tmp_path, {"src/repro/kernels/probe.py": """
        import functools
        import jax

        def _default_interpret():
            return jax.default_backend() != "tpu"     # per call: fine

        @functools.cache
        def fib(n):
            return n if n < 2 else fib(n - 1) + fib(n - 2)
    """})
    assert rules(r) == []


# ---------------------------------------------------------------------------
# JIT002 — host syncs inside traced code
# ---------------------------------------------------------------------------

def test_jit002_catches_host_syncs_in_jit_and_scan(tmp_path):
    r = run(tmp_path, {"src/repro/core/step.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return float(x) * 2

        def g(xs):
            def body(c, x):
                return c + x.item(), np.asarray(x)
            return jax.lax.scan(body, 0.0, xs)
    """})
    assert sorted(rules(r)) == ["JIT002", "JIT002", "JIT002"]


def test_jit002_exempts_shape_arithmetic_and_host_code(tmp_path):
    r = run(tmp_path, {"src/repro/core/step.py": """
        import jax

        @jax.jit
        def f(x):
            n = int(x.shape[0])        # static under tracing: fine
            return x * n

        def host(x):
            return float(x)            # not traced: fine
    """})
    assert rules(r) == []


# ---------------------------------------------------------------------------
# JIT003 — Python round/node loops behind a jitted-path docstring
# ---------------------------------------------------------------------------

def test_jit003_flags_round_loop_but_exempts_driver(tmp_path):
    r = run(tmp_path, {"src/repro/sim/fastpath.py": '''
        """Batched plane: the jitted lax.scan path over rounds."""

        def train(n_rounds):
            out = []
            for r in range(n_rounds):
                out.append(r)
            return out

        def driver_loop(n_rounds):
            for r in range(n_rounds):   # host driver by contract: exempt
                pass

        def train_reference(n_rounds):
            for r in range(n_rounds):   # retained reference: exempt
                pass
    '''})
    assert rules(r) == ["JIT003"]
    assert r.findings[0].scope == "train"


def test_jit003_silent_without_jitted_docstring(tmp_path):
    r = run(tmp_path, {"src/repro/sim/slowpath.py": '''
        """Host-side helpers."""

        def train(n_rounds):
            for r in range(n_rounds):
                pass
    '''})
    assert rules(r) == []


# ---------------------------------------------------------------------------
# DTYPE001 — float64 into jax
# ---------------------------------------------------------------------------

def test_dtype001_catches_float64_into_jax(tmp_path):
    r = run(tmp_path, {"src/repro/core/mix.py": """
        import numpy as np
        import jax.numpy as jnp

        def f(n):
            return jnp.zeros(n, dtype=np.float64)

        def host(n):
            return np.zeros(n, dtype=np.float64)   # numpy plane: fine
    """})
    assert rules(r) == ["DTYPE001"]
    assert r.findings[0].scope == "f"


# ---------------------------------------------------------------------------
# DTYPE002 — jax eigensolves outside enable_x64
# ---------------------------------------------------------------------------

def test_dtype002_flags_eig_outside_x64_scope(tmp_path):
    r = run(tmp_path, {"src/repro/core/spec.py": """
        import jax.numpy as jnp
        import numpy as np

        def lam(ws):
            return jnp.abs(jnp.linalg.eigvals(ws))

        def host(ws):
            return np.abs(np.linalg.eigvals(ws))   # numpy plane: fine
    """})
    assert rules(r) == ["DTYPE002"]
    assert r.findings[0].scope == "lam"


def test_dtype002_quiet_inside_x64_scope(tmp_path):
    r = run(tmp_path, {"src/repro/core/spec.py": """
        import jax
        import jax.numpy as jnp

        def lam(ws):
            with jax.enable_x64(True):
                def _eig(m):
                    return jnp.abs(jnp.linalg.eigvals(m))
                return jax.jit(jax.vmap(_eig))(ws)
    """})
    assert rules(r) == []


# ---------------------------------------------------------------------------
# PAL001 / PAL002 — Pallas kernel lint
# ---------------------------------------------------------------------------

def test_pal001_flags_hardcoded_interpret(tmp_path):
    r = run(tmp_path, {"src/repro/kernels/k.py": """
        import jax
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def op(x, interpret: bool = True):
            return pl.pallas_call(_kernel, out_shape=x, interpret=True)(x)
    """})
    # literal kwarg on pallas_call + literal default + missing router
    assert sorted(rules(r)) == ["PAL001", "PAL001", "PAL001"]


def test_pal001_quiet_on_default_interpret_routing(tmp_path):
    r = run(tmp_path, {"src/repro/kernels/k.py": """
        import functools
        import jax
        from jax.experimental import pallas as pl

        from ._backend import _default_interpret

        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @functools.partial(jax.jit, static_argnames=("interpret",))
        def _op(x, interpret: bool):
            return pl.pallas_call(_kernel, out_shape=x,
                                  interpret=interpret)(x)

        def op(x, interpret=None):
            if interpret is None:
                interpret = _default_interpret()
            return _op(x, bool(interpret))
    """})
    assert rules(r) == []


def test_pal002_flags_sub_fp32_accumulation(tmp_path):
    r = run(tmp_path, {"src/repro/kernels/k.py": """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from ._backend import _default_interpret

        def _kernel(x_ref, o_ref):
            acc = jnp.zeros(o_ref.shape, jnp.bfloat16)     # lossy
            acc = acc + x_ref[...].astype(jnp.float16)     # lossy
            o_ref[...] = acc.astype(o_ref.dtype)

        def op(x, interpret=None):
            if interpret is None:
                interpret = _default_interpret()
            return pl.pallas_call(_kernel, out_shape=x,
                                  interpret=interpret)(x)
    """})
    assert sorted(rules(r)) == ["PAL002", "PAL002"]


def test_pal002_allows_fp32_accumulate_with_output_cast(tmp_path):
    r = run(tmp_path, {"src/repro/kernels/k.py": """
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        from ._backend import _default_interpret

        def _kernel(x_ref, o_ref):
            acc = jnp.zeros(o_ref.shape, jnp.float32)
            acc = acc + x_ref[...].astype(jnp.float32)
            o_ref[...] = acc.astype(o_ref.dtype)           # output store: ok

        def op(x, interpret=None):
            if interpret is None:
                interpret = _default_interpret()
            return pl.pallas_call(_kernel, out_shape=x,
                                  interpret=interpret)(x)
    """})
    assert rules(r) == []


# ---------------------------------------------------------------------------
# PAR001 / PAR002 — parity-pin cross-reference
# ---------------------------------------------------------------------------

_SOLVER_SRC = """
    __all__ = ["solve_fast", "solve_fast_reference"]

    def solve_fast(cap):
        return cap * 2

    def solve_fast_reference(cap):
        return cap + cap
"""


def test_par001_missing_reference_sibling(tmp_path):
    r = run(tmp_path, {"src/repro/core/opt.py": """
        __all__ = ["solve_fast"]

        def solve_fast(cap):
            return cap * 2
    """})
    assert rules(r) == ["PAR001"]
    assert r.findings[0].scope == "solve_fast"


def test_par002_pair_without_test_pin(tmp_path):
    r = run(tmp_path, {"src/repro/core/opt.py": _SOLVER_SRC})
    assert rules(r) == ["PAR002"]


def test_parity_pin_satisfied_by_co_referencing_test(tmp_path):
    r = run(tmp_path, {
        "src/repro/core/opt.py": _SOLVER_SRC,
        "tests/test_opt.py": """
            from repro.core.opt import solve_fast, solve_fast_reference

            def test_parity():
                assert solve_fast(1) == solve_fast_reference(1)
        """,
    })
    assert rules(r) == []


def test_parity_rules_skip_private_and_non_parity_dirs(tmp_path):
    r = run(tmp_path, {
        "src/repro/core/opt.py": """
            __all__ = ["helper"]

            def _solve_hidden_batch(c):
                return c

            def helper(c):
                return c
        """,
        "src/repro/launch/runner.py": """
            def solve_everything(c):    # not core//sim/: out of scope
                return c
        """,
    })
    assert rules(r) == []


# ---------------------------------------------------------------------------
# Suppression, baseline, engine plumbing
# ---------------------------------------------------------------------------

def test_noqa_suppresses_only_named_rule(tmp_path):
    r = run(tmp_path, {"src/repro/sim/t.py": """
        import time

        def a():
            return time.time()   # repro: noqa[DET001]

        def b():
            return time.time()   # repro: noqa[JIT001]  (wrong id: still fires)

        def c():
            return time.time()   # repro: noqa
    """})
    assert rules(r) == ["DET001"]
    assert r.findings[0].scope == "b"


def test_baseline_round_trip_and_staleness(tmp_path):
    files = {"src/repro/sim/t.py": """
        import time

        def a():
            return time.time()
    """}
    r1 = run(tmp_path, files)
    assert [f.rule for f in r1.new] == ["DET001"]

    bpath = tmp_path / "analysis_baseline.json"
    write_baseline(r1.findings, bpath,
                   notes={r1.findings[0].fingerprint: "grandfathered"})
    r2 = analyze_repo(root=tmp_path)
    assert r2.clean and [f.rule for f in r2.baselined] == ["DET001"]
    assert load_baseline(bpath)[r1.findings[0].fingerprint]["note"] == \
        "grandfathered"

    # pay the debt down: the entry goes stale (and --ci would fail on it)
    (tmp_path / "src/repro/sim/t.py").write_text("def a():\n    return 0\n")
    r3 = analyze_repo(root=tmp_path)
    assert r3.clean and len(r3.stale) == 1


def test_baseline_counts_budget_duplicate_fingerprints(tmp_path):
    """Two findings on different lines of one scope share a fingerprint; the
    baseline budgets them by count, so a third occurrence is NEW."""
    files = {"src/repro/sim/t.py": """
        import numpy as np

        def a(seed):
            x = np.random.default_rng(seed)
            y = np.random.default_rng(seed)
            return x, y
    """}
    r1 = run(tmp_path, files)
    assert [f.rule for f in r1.new] == ["DET003", "DET003"]
    write_baseline(r1.findings, tmp_path / "analysis_baseline.json")

    (tmp_path / "src/repro/sim/t.py").write_text(textwrap.dedent("""
        import numpy as np

        def a(seed):
            x = np.random.default_rng(seed)
            y = np.random.default_rng(seed)
            z = np.random.default_rng(seed)
            return x, y, z
    """))
    r2 = analyze_repo(root=tmp_path)
    assert len(r2.baselined) == 2 and len(r2.new) == 1


def test_syntax_error_becomes_eng001(tmp_path):
    r = run(tmp_path, {"src/repro/core/broken.py": "def f(:\n"})
    assert rules(r) == ["ENG001"]


# ---------------------------------------------------------------------------
# CLI + CI gate
# ---------------------------------------------------------------------------

def test_cli_exit_codes_and_json(tmp_path, capsys):
    (tmp_path / "src/repro/sim").mkdir(parents=True)
    bad = tmp_path / "src/repro/sim/t.py"
    bad.write_text("import time\n\ndef a():\n    return time.time()\n")

    assert cli_main(["--root", str(tmp_path)]) == 1
    capsys.readouterr()
    assert cli_main(["--root", str(tmp_path), "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["counts"]["new"] == 1
    assert payload["new"][0]["rule"] == "DET001"

    assert cli_main(["--root", str(tmp_path), "--write-baseline"]) == 0
    capsys.readouterr()
    assert cli_main(["--root", str(tmp_path), "--ci"]) == 0

    # paying the debt makes the baseline stale: plain run passes, --ci fails
    bad.write_text("def a():\n    return 0\n")
    assert cli_main(["--root", str(tmp_path)]) == 0
    capsys.readouterr()
    assert cli_main(["--root", str(tmp_path), "--ci"]) == 1


def test_module_entrypoint_runs():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--list-rules"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={"PYTHONPATH": str(REPO_ROOT / "src"), "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0
    assert "DET001" in out.stdout and "PAR002" in out.stdout


def test_real_tree_is_clean_under_checked_in_baseline():
    """The acceptance gate, as a test: the shipped tree + shipped baseline
    must have zero new findings (and every baseline entry must justify
    itself with a note)."""
    result = analyze_repo(root=REPO_ROOT)
    assert result.clean, [f.render() for f in result.new]
    assert not result.stale
    for entry in load_baseline(REPO_ROOT / "analysis_baseline.json").values():
        assert entry["note"], f"baseline entry without a note: {entry}"
