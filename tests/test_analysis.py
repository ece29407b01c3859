"""Tests for ``repro.analysis`` -- the AST invariant linter.

Three layers:

* per-rule fixture triplets: a *positive* file that must trip the rule,
  a *negative* file that must not, and a *pragma'd* positive whose
  finding must survive in the report as suppressed-with-reason;
* the tier-1 self-run: the live ``src/repro`` tree must be clean (zero
  unsuppressed violations) -- this is the contract that a PR breaking a
  serving invariant fails CI;
* the CLI: exit 0 on clean, 1 on violations, 2 on usage errors.

Fixture files are written into ``tmp_path`` subdirectories matching the
path scoping of the rules (``core/``, ``kernels/``, ...).
"""

import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis import analyze_paths, rule_names

REPO_ROOT = Path(__file__).resolve().parents[1]
SRC = REPO_ROOT / "src"


def run_fixture(tmp_path, files, select=None):
    """Write ``{relpath: source}`` under tmp_path and analyze it."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return analyze_paths([str(tmp_path)], select=select)


def active_of(report, rule):
    return [v for v in report.active if v.rule == rule]


def suppressed_of(report, rule):
    return [v for v in report.suppressed if v.rule == rule]


# ---------------------------------------------------------------------------
# registry / plumbing
# ---------------------------------------------------------------------------

def test_all_five_rules_registered():
    assert set(rule_names()) == {
        "donation-aliasing", "f64-discipline", "hot-path-sync",
        "recompile-hazard", "sentinel-mask"}


def test_syntax_error_reported_not_raised(tmp_path):
    report = run_fixture(tmp_path, {"broken.py": "def f(:\n"})
    assert [v.rule for v in report.active] == ["parse"]
    assert not report.ok


def test_unknown_select_raises_keyerror(tmp_path):
    (tmp_path / "a.py").write_text("x = 1\n")
    with pytest.raises(KeyError):
        analyze_paths([str(tmp_path)], select=["no-such-rule"])


# ---------------------------------------------------------------------------
# rule 1: donation-aliasing
# ---------------------------------------------------------------------------

_DONATION_POS = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scat(buf, rows):
        return buf.at[rows].set(False)

    def caller(state, rows):
        out = scat(state.buf, rows)
        return state.buf.sum() + out.sum()   # stale read of donated buf
"""

_DONATION_NEG = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scat(buf, rows):
        return buf.at[rows].set(False)

    def caller(state, rows):
        state.buf = scat(state.buf, rows)    # rebind at the call site
        return state.buf.sum()
"""


def test_donation_positive(tmp_path):
    report = run_fixture(tmp_path, {"m.py": _DONATION_POS},
                         select=["donation-aliasing"])
    vs = active_of(report, "donation-aliasing")
    assert len(vs) == 1
    assert "state.buf" in vs[0].message and "donated" in vs[0].message


def test_donation_negative(tmp_path):
    report = run_fixture(tmp_path, {"m.py": _DONATION_NEG},
                         select=["donation-aliasing"])
    assert active_of(report, "donation-aliasing") == []


def test_donation_pragma_suppresses_with_reason(tmp_path):
    src = _DONATION_POS.replace(
        "return state.buf.sum() + out.sum()   # stale read of donated buf",
        "return state.buf.sum() + out.sum()  "
        "# grit-lint: disable=donation-aliasing -- buffer re-uploaded below")
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["donation-aliasing"])
    assert active_of(report, "donation-aliasing") == []
    sup = suppressed_of(report, "donation-aliasing")
    assert len(sup) == 1
    assert sup[0].reason == "buffer re-uploaded below"


def test_donation_rebind_before_read_is_clean(tmp_path):
    src = """
        import jax

        def g(buf):
            return buf

        scat = jax.jit(g, donate_argnums=(0,))

        def caller(buf):
            scat(buf)
            buf = make_new()
            return buf.sum()
    """
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["donation-aliasing"])
    assert active_of(report, "donation-aliasing") == []


# ---------------------------------------------------------------------------
# rule 2: f64-discipline
# ---------------------------------------------------------------------------

_PRECISION_POS = """
    import numpy as np

    def decide(d2, eps):
        eps2 = np.float32(eps) ** 2          # f32 cast in core/
        return d2 <= eps2
"""


def test_precision_positive_in_core(tmp_path):
    report = run_fixture(tmp_path, {"core/foo.py": _PRECISION_POS},
                         select=["f64-discipline"])
    vs = active_of(report, "f64-discipline")
    assert vs and any("float32" in v.message for v in vs)


def test_precision_out_of_scope_is_clean(tmp_path):
    # the same source outside core//index/ is none of this rule's business
    report = run_fixture(tmp_path, {"serve/foo.py": _PRECISION_POS},
                         select=["f64-discipline"])
    assert active_of(report, "f64-discipline") == []


def test_precision_negative_f64_in_core(tmp_path):
    src = """
        import numpy as np

        def decide(d2, eps):
            eps2 = np.float64(eps) ** 2
            return d2 <= eps2
    """
    report = run_fixture(tmp_path, {"core/foo.py": src},
                         select=["f64-discipline"])
    assert active_of(report, "f64-discipline") == []


def test_precision_allowlisted_dispatch_is_clean(tmp_path):
    src = """
        import jax.numpy as jnp

        def fast_merging_masked(si, sj, eps):
            si = si.astype(jnp.float32)
            return si
    """
    report = run_fixture(tmp_path, {"core/merging.py": src},
                         select=["f64-discipline"])
    assert active_of(report, "f64-discipline") == []


def test_precision_mixed_compare(tmp_path):
    src = """
        import numpy as np

        def decide(d2_exact, eps):
            t = np.float32(eps)
            return d2_exact <= t             # mixed f32/f64 compare
    """
    report = run_fixture(tmp_path, {"index/foo.py": src},
                         select=["f64-discipline"])
    msgs = [v.message for v in active_of(report, "f64-discipline")]
    assert any("mixes" in m for m in msgs)


def test_precision_pragma(tmp_path):
    src = _PRECISION_POS.replace(
        "eps2 = np.float32(eps) ** 2          # f32 cast in core/",
        "eps2 = np.float32(eps) ** 2  "
        "# grit-lint: disable=f64-discipline -- certain-only path, band applied")
    report = run_fixture(tmp_path, {"core/foo.py": src},
                         select=["f64-discipline"])
    assert active_of(report, "f64-discipline") == []
    sup = suppressed_of(report, "f64-discipline")
    assert sup and sup[0].reason == "certain-only path, band applied"


# ---------------------------------------------------------------------------
# rule 3: recompile-hazard
# ---------------------------------------------------------------------------

_RECOMPILE_POS = """
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops as kernel_ops

    def f(q):
        n = q.shape[0]
        buf = np.zeros((n, 4))               # raw data-dependent shape
        return kernel_ops.eps_count_batch(jnp.asarray(buf))
"""

_RECOMPILE_NEG = """
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels import ops as kernel_ops

    def _pow2_at_least(n, lo=8):
        return max(lo, 1 << (int(n) - 1).bit_length())

    def f(q):
        n = q.shape[0]
        cap = _pow2_at_least(n)
        buf = np.zeros((cap, 4))             # pow2-bucketed shape
        return kernel_ops.eps_count_batch(jnp.asarray(buf))
"""


def test_recompile_positive(tmp_path):
    report = run_fixture(tmp_path, {"m.py": _RECOMPILE_POS},
                         select=["recompile-hazard"])
    vs = active_of(report, "recompile-hazard")
    assert len(vs) == 1 and "'buf'" in vs[0].message


def test_recompile_negative_bucketed(tmp_path):
    report = run_fixture(tmp_path, {"m.py": _RECOMPILE_NEG},
                         select=["recompile-hazard"])
    assert active_of(report, "recompile-hazard") == []


def test_recompile_static_argnames_array(tmp_path):
    src = """
        import functools
        import numpy as np
        import jax

        @functools.partial(jax.jit, static_argnames=("block",))
        def k(x, *, block):
            return x

        def g(x):
            return k(x, block=np.asarray([1, 2]))
    """
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["recompile-hazard"])
    vs = active_of(report, "recompile-hazard")
    assert vs and "static argument 'block'" in vs[0].message


def test_recompile_static_argnames_scalar_is_clean(tmp_path):
    src = """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("block",))
        def k(x, *, block):
            return x

        def g(x):
            return k(x, block=128)
    """
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["recompile-hazard"])
    assert active_of(report, "recompile-hazard") == []


def test_recompile_pragma(tmp_path):
    src = _RECOMPILE_POS.replace(
        "        return kernel_ops.eps_count_batch(jnp.asarray(buf))",
        "        # grit-lint: disable=recompile-hazard -- cold path, runs once\n"
        "        return kernel_ops.eps_count_batch(jnp.asarray(buf))")
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["recompile-hazard"])
    assert active_of(report, "recompile-hazard") == []
    sup = suppressed_of(report, "recompile-hazard")
    assert sup and sup[0].reason == "cold path, runs once"


# ---------------------------------------------------------------------------
# rule 4: hot-path-sync
# ---------------------------------------------------------------------------

_HOTSYNC_POS = """
    import numpy as np
    import jax.numpy as jnp

    class ClusterServer:
        def step(self, batch):
            return helper(batch)

    def helper(batch):
        d2dev = jnp.zeros(4)
        return float(np.asarray(d2dev))      # sync inside the hot graph
"""

_HOTSYNC_NEG = """
    import numpy as np
    import jax.numpy as jnp

    class ClusterServer:
        def step(self, batch):
            return pack(batch)

    def pack(batch):
        return np.asarray(batch, np.int32)   # host value: not a sync

    def offline_report(res):
        d2dev = jnp.zeros(4)
        return float(np.asarray(d2dev))      # not reachable from step
"""


def test_hotsync_positive(tmp_path):
    report = run_fixture(tmp_path, {"m.py": _HOTSYNC_POS},
                         select=["hot-path-sync"])
    vs = active_of(report, "hot-path-sync")
    assert vs and "helper()" in vs[0].message


def test_hotsync_negative_unreachable_and_host_values(tmp_path):
    report = run_fixture(tmp_path, {"m.py": _HOTSYNC_NEG},
                         select=["hot-path-sync"])
    assert active_of(report, "hot-path-sync") == []


def test_hotsync_block_until_ready_flags(tmp_path):
    src = """
        class ClusterServer:
            def step(self, batch):
                out = launch(batch)
                out.block_until_ready()
                return out
    """
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["hot-path-sync"])
    vs = active_of(report, "hot-path-sync")
    assert vs and "block_until_ready" in vs[0].message


def test_hotsync_pragma(tmp_path):
    src = _HOTSYNC_POS.replace(
        "return float(np.asarray(d2dev))      # sync inside the hot graph",
        "return float(np.asarray(d2dev))  "
        "# grit-lint: disable=hot-path-sync -- the stage's intended block point")
    report = run_fixture(tmp_path, {"m.py": src},
                         select=["hot-path-sync"])
    assert active_of(report, "hot-path-sync") == []
    sup = suppressed_of(report, "hot-path-sync")
    assert sup and sup[0].reason == "the stage's intended block point"


# ---------------------------------------------------------------------------
# rule 5: sentinel-mask
# ---------------------------------------------------------------------------

_SENTINEL_POS = """
    import jax.numpy as jnp

    def row_min_wrapper(d2):
        return jnp.min(d2, axis=-1)          # raw reduce over padded buf
"""

_SENTINEL_NEG = """
    import jax.numpy as jnp

    def row_min_wrapper(d2, valid):
        d2m = jnp.where(valid, d2, jnp.inf)
        return jnp.min(d2m, axis=-1)
"""


def test_sentinel_positive(tmp_path):
    report = run_fixture(tmp_path, {"kernels/foo.py": _SENTINEL_POS},
                         select=["sentinel-mask"])
    vs = active_of(report, "sentinel-mask")
    assert len(vs) == 1 and "validity" in vs[0].message


def test_sentinel_negative_masked(tmp_path):
    report = run_fixture(tmp_path, {"kernels/foo.py": _SENTINEL_NEG},
                         select=["sentinel-mask"])
    assert active_of(report, "sentinel-mask") == []


def test_sentinel_out_of_scope_is_clean(tmp_path):
    report = run_fixture(tmp_path, {"serve/foo.py": _SENTINEL_POS},
                         select=["sentinel-mask"])
    assert active_of(report, "sentinel-mask") == []


def test_sentinel_kernel_body_exempt(tmp_path):
    src = """
        import jax.numpy as jnp

        def _row_min_kernel(a_ref, out_ref):
            out_ref[...] = jnp.min(a_ref[...], axis=-1)
    """
    report = run_fixture(tmp_path, {"kernels/foo.py": src},
                         select=["sentinel-mask"])
    assert active_of(report, "sentinel-mask") == []


def test_sentinel_pragma(tmp_path):
    src = _SENTINEL_POS.replace(
        "return jnp.min(d2, axis=-1)          # raw reduce over padded buf",
        "return jnp.min(d2, axis=-1)  "
        "# grit-lint: disable=sentinel-mask -- caller FAR-folds per contract")
    report = run_fixture(tmp_path, {"kernels/foo.py": src},
                         select=["sentinel-mask"])
    assert active_of(report, "sentinel-mask") == []
    sup = suppressed_of(report, "sentinel-mask")
    assert sup and sup[0].reason == "caller FAR-folds per contract"


# ---------------------------------------------------------------------------
# pragma meta-rule
# ---------------------------------------------------------------------------

def test_reasonless_pragma_reported_and_does_not_suppress(tmp_path):
    src = _SENTINEL_POS.replace(
        "return jnp.min(d2, axis=-1)          # raw reduce over padded buf",
        "return jnp.min(d2, axis=-1)  # grit-lint: disable=sentinel-mask")
    report = run_fixture(tmp_path, {"kernels/foo.py": src},
                         select=["sentinel-mask"])
    assert active_of(report, "sentinel-mask"), \
        "reasonless pragma must not suppress"
    assert active_of(report, "pragma"), \
        "reasonless pragma must itself be reported"


def test_unknown_rule_pragma_reported(tmp_path):
    src = _SENTINEL_POS.replace(
        "return jnp.min(d2, axis=-1)          # raw reduce over padded buf",
        "return jnp.min(d2, axis=-1)  "
        "# grit-lint: disable=no-such-rule -- whatever")
    report = run_fixture(tmp_path, {"kernels/foo.py": src},
                         select=["sentinel-mask"])
    assert active_of(report, "sentinel-mask")
    assert any("unknown rule" in v.message
               for v in active_of(report, "pragma"))


# ---------------------------------------------------------------------------
# tier-1 self-run: the live tree is the contract
# ---------------------------------------------------------------------------

def test_live_tree_is_clean():
    report = analyze_paths([str(SRC / "repro")])
    assert report.files_checked > 50
    assert report.ok, "live src/repro must have zero unsuppressed " \
        "violations:\n" + report.format()
    # every escape hatch in the tree carries a written justification
    assert report.suppressed, "the known block points should be pragma'd"
    for v in report.suppressed:
        assert v.reason.strip(), v.format()


# ---------------------------------------------------------------------------
# CLI exit codes
# ---------------------------------------------------------------------------

def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC) + os.pathsep + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env, timeout=120)


def test_cli_exit_zero_on_clean_file(tmp_path):
    p = tmp_path / "clean.py"
    p.write_text("def f(x):\n    return x + 1\n")
    proc = _run_cli("--check", str(p))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_cli_exit_nonzero_on_violation(tmp_path):
    p = tmp_path / "kernels"
    p.mkdir()
    (p / "bad.py").write_text(textwrap.dedent(_SENTINEL_POS))
    proc = _run_cli("--check", str(tmp_path))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "sentinel-mask" in proc.stdout


def test_cli_usage_error_without_paths():
    proc = _run_cli()
    assert proc.returncode == 2


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for name in rule_names():
        assert name in proc.stdout


# ---------------------------------------------------------------------------
# external tools (CI lint job); skipped where not installed
# ---------------------------------------------------------------------------

@pytest.mark.skipif(shutil.which("ruff") is None,
                    reason="ruff not installed (CI lint job runs it)")
def test_ruff_clean():
    proc = subprocess.run(
        ["ruff", "check", "src", "tests"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


@pytest.mark.skipif(shutil.which("mypy") is None,
                    reason="mypy not installed (CI lint job runs it)")
def test_mypy_clean():
    proc = subprocess.run(
        ["mypy"], capture_output=True, text=True, cwd=REPO_ROOT,
        timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
