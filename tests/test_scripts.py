"""Every file left in ``scripts/`` still matches the tree: a ``.py``
compiles and each ``flink_ms_tpu`` name it imports resolves; a ``.sh`` passes
``bash -n`` and each ``python -m`` module and script path it names exists.
No test runs these drivers, so without this a rename in the package leaves
them broken until an operator finds out."""

import ast
import importlib
import importlib.util
import pathlib
import re
import subprocess

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPTS = sorted(p for p in (ROOT / "scripts").iterdir()
                 if p.suffix in (".py", ".sh"))

_FROM_IMPORT = re.compile(
    r"^\s*from\s+(flink_ms_tpu[\w.]*)\s+import\s+\(?([\w\s,]+)", re.M)


def _package_imports_py(source: str, filename: str):
    """(module, name or None) for every flink_ms_tpu import in `source`."""
    for node in ast.walk(ast.parse(source, filename=filename)):
        if isinstance(node, ast.ImportFrom) and node.level == 0 \
                and (node.module or "").startswith("flink_ms_tpu"):
            for alias in node.names:
                yield node.module, alias.name
        elif isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith("flink_ms_tpu"):
                    yield alias.name, None


def _package_imports_sh(source: str):
    """The same for the Python heredocs a shell script feeds to ``$PY -``."""
    for module, names in _FROM_IMPORT.findall(source):
        for name in names.split(","):
            name = name.split(" as ")[0].strip()
            if name:
                yield module, name


def _assert_resolves(module: str, name, where: str):
    mod = importlib.import_module(module)
    if name is None or hasattr(mod, name):
        return
    # ``from package import submodule``
    assert importlib.util.find_spec(f"{module}.{name}") is not None, (
        f"{where}: {module} has no {name!r}")


def test_every_script_is_covered():
    assert SCRIPTS, "scripts/ is empty or missing"
    others = [p.name for p in (ROOT / "scripts").iterdir()
              if p.is_file() and p not in SCRIPTS]
    assert not others, f"files this test cannot check: {others}"


@pytest.mark.parametrize("path", SCRIPTS, ids=lambda p: p.name)
def test_script_matches_the_tree(path):
    source = path.read_text()
    where = f"scripts/{path.name}"
    if path.suffix == ".py":
        compile(source, str(path), "exec")
        imports = list(_package_imports_py(source, str(path)))
        assert imports, f"{where} imports nothing from flink_ms_tpu"
    else:
        done = subprocess.run(["bash", "-n", str(path)],
                              capture_output=True, text=True)
        assert done.returncode == 0, done.stderr
        modules = re.findall(r"-m\s+(flink_ms_tpu[\w.]+)", source)
        assert modules, f"{where} runs no flink_ms_tpu module"
        for module in modules:
            assert importlib.util.find_spec(module) is not None, (
                f"{where}: python -m {module} does not exist")
        for rel in re.findall(r"\bscripts/[\w./-]+\.(?:py|sh)\b", source):
            assert (ROOT / rel).is_file(), f"{where} names {rel}: no such file"
        imports = list(_package_imports_sh(source))
    for module, name in imports:
        _assert_resolves(module, name, where)
