"""Every registered figure must be deterministic: same seed, same rows.

Generalizes the old fig23-only CI determinism check to the whole
registry.  Each figure runs twice on the smoke fast path and the emitted
rows must serialize byte-identically — ``*/wall`` timing rows are the
only sanctioned nondeterminism and are excluded before comparison.  A
final subprocess test replays the full ``benchmarks.run --smoke --json``
sweep in two fresh interpreters, so hash randomization or import-order
effects can't hide behind in-process state.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks import figures as figures_mod  # noqa: E402
from benchmarks.figures import ALL_FIGURES  # noqa: E402


def _rows_json(fig):
    """Run one figure on the smoke path and serialize its rows."""
    old_smoke, old_seed = figures_mod.SMOKE, figures_mod.SEED
    figures_mod.SMOKE, figures_mod.SEED = True, 0
    try:
        rows = fig()
    finally:
        figures_mod.SMOKE, figures_mod.SEED = old_smoke, old_seed
    return json.dumps([[name, float(val), str(der)]
                       for name, val, der in rows])


@pytest.mark.slow
@pytest.mark.parametrize("fig", ALL_FIGURES, ids=lambda f: f.__name__)
def test_figure_is_deterministic_under_smoke(fig):
    assert _rows_json(fig) == _rows_json(fig), (
        f"{fig.__name__} emitted different rows for the same seed")


@pytest.mark.slow
def test_full_smoke_sweep_is_deterministic_across_interpreters():
    def sweep():
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (os.path.join(REPO, "src"), REPO,
                        env.get("PYTHONPATH", "")) if p)
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--only", "fig",
             "--smoke", "--json"],
            cwd=REPO, env=env, capture_output=True, text=True, check=True)
        d = json.loads(out.stdout)
        assert d["schema"] == "figures/v2"
        return [r for r in d["rows"] if not r["name"].endswith("/wall")]

    a, b = sweep(), sweep()
    assert a == b, "smoke sweep differs between two fresh interpreters"


def test_montecarlo_seed_runs_never_reach_the_kernels():
    """Seed subprocesses run figures only: the rows that an unfiltered
    ``benchmarks.run`` adds besides the figures do not depend on the
    seed."""
    from benchmarks.montecarlo import seed_command
    cmd = seed_command(3, "", smoke=True)
    only = cmd[cmd.index("--only") + 1]
    assert only == "fig" and "--smoke" in cmd
    assert all(only in f.__name__ for f in ALL_FIGURES)
    cmd = seed_command(3, "fig19", smoke=False)
    assert cmd[cmd.index("--only") + 1] == "fig19"
