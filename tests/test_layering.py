"""Which layer imports which: the facade reads the engine's counters
through the engine, and the simulator loads no Pallas and none of the LM
stack's kernels."""
import ast
import os
import pathlib
import subprocess
import sys

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def _imports(path):
    """The module names a source file imports, at any depth."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_union_imports_nothing_of_the_kernels():
    files = sorted((SRC / "repro" / "union").rglob("*.py"))
    assert files
    found = {(str(f.relative_to(SRC)), name) for f in files
             for name in _imports(f)
             if name == "repro.kernels" or name.startswith("repro.kernels.")}
    assert not found


def test_import_union_loads_no_pallas():
    code = ("import sys, repro.union\n"
            "print('\\n'.join(m for m in sys.modules if 'pallas' in m\n"
            "                 or m == 'repro.kernels.ssd_scan'))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(SRC))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    assert out.stdout.split() == []
