"""The invariant linter is itself under test: every rule is proven live by
a fixture that makes it fire (a linter whose rules can't fire is just a
green checkmark), suppression markers narrow it back down, and the real
tree comes up clean — the same contract the CI ``analysis`` lane enforces
with ``python -m repro.analysis.check --strict``.
"""

import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import astlint, programlint
from repro.analysis.base import all_rules, skip_markers
from repro.analysis.check import main as check_main, run_checks

REPO_ROOT = Path(__file__).resolve().parents[1]


def _ids(findings):
    return sorted({f.rule_id for f in findings})


def _mini_repo(tmp_path, files):
    """Materialize {repo-relative path: source} as a fake checkout."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src, encoding="utf-8")
    return tmp_path


# --------------------------------------------------------------------------
# registry / marker plumbing
# --------------------------------------------------------------------------

def test_rule_registry_complete():
    rules = all_rules()
    ast_ids = {r for r in rules if r.startswith("AST")}
    prg_ids = {r for r in rules if r.startswith("PRG")}
    assert len(ast_ids) >= 4 and len(prg_ids) >= 3
    for r in rules.values():
        assert r.invariant and r.guarded_since


def test_skip_marker_parsing():
    src = (
        "x = 1  # lint: skip[AST001]\n"
        "# lint: skip[AST002, PRG001]\n"
        "y = 2\n"
    )
    skips = skip_markers(src)
    assert skips[1] == {"AST001"}
    assert skips[2] == {"AST002", "PRG001"}
    assert skips[3] == {"AST002", "PRG001"}   # comment covers next line


# --------------------------------------------------------------------------
# AST rules: one violating fixture each, plus suppression
# --------------------------------------------------------------------------

def test_ast001_fires_on_bypassed_dispatch(tmp_path):
    root = _mini_repo(tmp_path, {
        "src/repro/models/bad.py": (
            "import jax.numpy as jnp\n"
            "def f(x, w_gate):\n"
            "    return jnp.einsum('nd,de->ne', x, w_gate.astype(x.dtype))\n"
            "def g(x, weights):\n"
            "    return x @ weights\n"
            "def dense_apply(x, w):\n"
            "    return jnp.dot(x, w)\n"   # the dispatch point itself: exempt
        ),
    })
    fs = astlint.run(root, rules={"AST001"})
    assert _ids(fs) == ["AST001"] and len(fs) == 2
    assert {f.line for f in fs} == {3, 5}


def test_ast001_skip_marker_suppresses(tmp_path):
    root = _mini_repo(tmp_path, {
        "src/repro/models/ok.py": (
            "import jax.numpy as jnp\n"
            "def f(x, conv_w):\n"
            "    # lint: skip[AST001] depthwise tap, not a matmul\n"
            "    return jnp.einsum('bwc,wc->bc', x, conv_w)\n"
        ),
    })
    assert astlint.run(root, rules={"AST001"}) == []


def test_ast002_fires_on_clock_and_global_rng(tmp_path):
    root = _mini_repo(tmp_path, {
        "src/repro/kernels/bad.py": (
            "import time\n"
            "import numpy as np\n"
            "def f():\n"
            "    t = time.perf_counter()\n"
            "    x = np.random.uniform(size=3)\n"
            "    rng = np.random.default_rng(0)\n"   # seeded: allowed
            "    return t, x, rng\n"
        ),
    })
    fs = astlint.run(root, rules={"AST002"})
    assert _ids(fs) == ["AST002"] and {f.line for f in fs} == {4, 5}


def test_ast003_fires_on_unlocked_mailbox(tmp_path):
    root = _mini_repo(tmp_path, {
        "src/repro/launch/badrouter.py": (
            "class R:\n"
            "    def __init__(self, q):\n"
            "        self.commands = q\n"              # __init__: exempt\n
            "    def submit(self, rep, cmd):\n"
            "        rep.commands.put(('submit', cmd))\n"      # fires
            "    def nudge(self, rep):\n"
            "        rep.commands.put(('nudge', None, None))\n"  # exempt
            "    def drain(self, rep):\n"
            "        rep.commands.get_nowait()\n"              # fires
            "    def locked(self, rep, cmd):\n"
            "        with self._lock:\n"
            "            rep.commands.put(('submit', cmd))\n"  # exempt
        ),
    })
    fs = astlint.run(root, rules={"AST003"})
    assert _ids(fs) == ["AST003"] and {f.line for f in fs} == {5, 9}


def test_ast004_fires_on_incomplete_kernel_package(tmp_path):
    root = _mini_repo(tmp_path, {
        "src/repro/kernels/mykern/kernel.py": "X = 1\n",
        "tests/test_other.py": "import repro\n",
    })
    fs = astlint.run(root, rules={"AST004"})
    msgs = " ".join(f.message for f in fs)
    assert _ids(fs) == ["AST004"] and len(fs) == 3
    assert "ref.py" in msgs and "ops.py" in msgs and "parity test" in msgs


def test_ast005_fires_on_unknown_rule_id(tmp_path):
    root = _mini_repo(tmp_path, {
        "src/repro/models/stale.py": "x = 1  # lint: skip[AST999]\n",
    })
    fs = astlint.run(root, rules={"AST005"})
    assert _ids(fs) == ["AST005"] and "AST999" in fs[0].message


# --------------------------------------------------------------------------
# program rules: violating traces each
# --------------------------------------------------------------------------

def _report(fn, *args, donated=False, name="fixture", compile_=False):
    tr = jax.jit(fn).trace(*args)
    low = tr.lower()
    return programlint.EntryReport(
        name, tr.jaxpr, low.as_text(),
        low.compile().as_text() if compile_ else None, donated)


def test_prg001_fires_on_weight_sized_const():
    big = jnp.zeros((512, 256), jnp.float32)    # 128Ki elems, closed over

    def f(x):
        return x @ big

    fs = programlint._check_dtypes(_report(f, jnp.ones((4, 512), jnp.float32)))
    assert _ids(fs) == ["PRG001"]
    assert any("constant" in f.message for f in fs)


def test_prg001_fires_on_f64():
    with jax.enable_x64(True):
        fs = programlint._check_dtypes(
            _report(lambda x: x * 2.0, jnp.ones((4,), jnp.float64)))
    assert _ids(fs) == ["PRG001"]
    assert any("float64" in f.message or "f64" in f.message for f in fs)


def test_prg002_fires_on_callback_in_scan():
    def f(x):
        def body(c, _):
            jax.debug.print("step {c}", c=c.sum())
            return c * 2.0, ()
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    fs = programlint._check_callbacks(_report(f, jnp.ones((4,), jnp.float32)))
    assert _ids(fs) == ["PRG002"]


def test_prg003_fires_on_dropped_donation():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def f(x):
        return jnp.zeros((3, 5), jnp.float32)   # no output aliases x

    tr = f.trace(jnp.ones((4,), jnp.float32))
    rep = programlint.EntryReport("fixture", tr.jaxpr,
                                  tr.lower().as_text(), None, donated=True)
    fs = programlint._check_donation(rep)
    assert _ids(fs) == ["PRG003"]


def test_prg003_clean_on_honored_donation():
    @functools.partial(jax.jit, donate_argnums=(0,))
    def f(x):
        return x + 1.0

    tr = f.trace(jnp.ones((4,), jnp.float32))
    low = tr.lower()
    rep = programlint.EntryReport("fixture", tr.jaxpr, low.as_text(),
                                  low.compile().as_text(), donated=True)
    assert programlint._check_donation(rep) == []


def test_prg004_fires_on_vmem_overflow():
    huge = programlint.TTShape(
        "huge", 512, ((8192, 4096), (4096, 8192, 1)), 1, ("f32", "f32"))
    fs = programlint.check_vmem_shapes([huge])
    assert _ids(fs) == ["PRG004"]
    assert "unfused fallback" in fs[0].message


def test_prg004_registered_shapes_fit():
    assert programlint.check_vmem_shapes() == []


# --------------------------------------------------------------------------
# the real tree is clean
# --------------------------------------------------------------------------

def test_repo_ast_layer_clean():
    fs = astlint.run(REPO_ROOT)
    assert fs == [], "\n".join(f.render() for f in fs)


def test_repo_program_entry_clean():
    # one cheap real entry end to end; the CI analysis lane sweeps them all
    fs = programlint.run(fast=True, entries=["admission"])
    fs += programlint.check_vmem_shapes()
    assert fs == [], "\n".join(f.render() for f in fs)


@pytest.mark.slow
def test_repo_program_layer_clean_fast_sweep():
    fs = programlint.run(fast=True)
    assert fs == [], "\n".join(f.render() for f in fs)


def test_check_cli_ast_layer(capsys):
    rc = check_main(["--strict", "--layer", "ast", "--root", str(REPO_ROOT)])
    out = capsys.readouterr().out
    assert rc == 0 and "clean" in out


def test_check_cli_list_rules(capsys):
    rc = check_main(["--list-rules"])
    out = capsys.readouterr().out
    assert rc == 0
    for rid in ("AST001", "AST004", "PRG001", "PRG004"):
        assert rid in out


def test_run_checks_rule_filter():
    fs = run_checks(layer="ast", rules=["AST004"], root=str(REPO_ROOT))
    assert fs == []
