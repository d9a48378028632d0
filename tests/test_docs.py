"""The CI docs job, runnable locally: dead intra-repo links/paths in
README + docs/*.md fail, and the documented quickstart commands must
still parse (--help / --list dry form).  tools/check_docs.py is the
single implementation; this wrapper keeps it in the tier-1 loop."""
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.mark.slow
def test_docs_health():
    r = subprocess.run([sys.executable, str(ROOT / "tools/check_docs.py")],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"\n{r.stdout}\n{r.stderr}"


def test_docs_exist_and_linked():
    """Cheap tier-1 subset: the docs tree exists and README links it."""
    assert (ROOT / "docs/ARCHITECTURE.md").exists()
    assert (ROOT / "docs/BENCHMARKS.md").exists()
    readme = (ROOT / "README.md").read_text()
    assert "docs/ARCHITECTURE.md" in readme
    assert "docs/BENCHMARKS.md" in readme


def test_bench_run_fails_when_any_scenario_raises(capsys):
    """benchmarks/run.py exits non-zero when a scenario raised, in a
    full run as well as for a scenario requested by name."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_pipo_bench_run_exit", ROOT / "benchmarks" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    def fine():
        """passes"""

    def broken():
        """raises"""
        raise RuntimeError("scenario failed")

    mod.BENCHES = [fine, broken]
    assert mod.main([]) == 1
    assert mod.main(["broken"]) == 1
    assert mod.main(["fine"]) == 0
    assert "broken_ERROR" in capsys.readouterr().out
