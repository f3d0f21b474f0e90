"""The option surface, and which way the benchmark's arrow points.

`cellbench/` measures and imports `cloud_tpu`; nothing in the package
knows a benchmark exists, no environment name of a deleted benchmark is
left in a source or workflow file, and every `CLOUD_TPU_*` name the
program holds is a line of README.md's "Environment" table, so the count
can only change knowingly.
"""

import ast
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Spelled in two parts so that this file holds no such name itself.
_BENCH_NAME = re.compile(r"\b" + "BENCH" + r"_[A-Z0-9_]*")
_ENV_NAME = re.compile(r"CLOUD_TPU_[A-Z0-9_]*[A-Z0-9]")
_TABLE_ROW = re.compile(r"^\| `(CLOUD_TPU_[A-Z0-9_]+)` \| .+ \| "
                        r"(deployment|switch) \|$")


def _files(root, keep):
    """Files under `root` (or `root` itself) that `keep(name)` takes;
    hidden and output directories are not sources."""
    if os.path.isfile(root):
        return [root]
    found = []
    for folder, dirs, names in os.walk(root):
        dirs[:] = [d for d in dirs
                   if d == ".github" or not (
                       d.startswith(".") or d in ("chiprun_out",
                                                  "__pycache__"))]
        found += [os.path.join(folder, n) for n in names if keep(n)]
    return found


def _read(path):
    with open(path, encoding="utf-8") as fh:
        return fh.read()


def _program_sources():
    """What README.md's table and ROADMAP 3.8's count cover."""
    paths = []
    for root in ("cloud_tpu", "chip_smoke.py", "__graft_entry__.py"):
        paths += _files(os.path.join(REPO, root),
                        lambda n: n.endswith(".py"))
    return paths


def test_no_deleted_benchmark_option_is_named():
    sources = _files(REPO, lambda n: n.endswith((".py", ".yml"))
                     or n in ("Makefile", ".pre-commit-config.yaml"))
    assert len(sources) > 200
    named = {os.path.relpath(path, REPO): sorted(set(
        _BENCH_NAME.findall(_read(path)))) for path in sources}
    assert {p: n for p, n in named.items() if n} == {}


def test_environment_table_lists_what_the_program_reads():
    held = set()
    for path in _program_sources():
        for node in ast.walk(ast.parse(_read(path))):
            if (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and _ENV_NAME.fullmatch(node.value)):
                held.add(node.value)
    readme = _read(os.path.join(REPO, "README.md"))
    section = readme.split("\n## Environment\n", 1)[1].split("\n## ")[0]
    rows = [line for line in section.splitlines()
            if line.startswith("| `")]
    listed = [_TABLE_ROW.match(line) for line in rows]
    assert all(listed), [r for r, m in zip(rows, listed) if not m]
    names = [m.group(1) for m in listed]
    assert len(names) == len(set(names))
    assert set(names) == held


def test_the_package_imports_no_benchmark():
    outside = {"cellbench", "bench", "benchmarks"}
    imported = []
    for path in _files(os.path.join(REPO, "cloud_tpu"),
                       lambda n: n.endswith(".py")):
        for node in ast.walk(ast.parse(_read(path))):
            if isinstance(node, ast.Import):
                modules = [alias.name for alias in node.names]
            elif isinstance(node, ast.ImportFrom) and not node.level:
                modules = [node.module]
            else:
                continue
            imported += [(os.path.relpath(path, REPO), m)
                         for m in modules
                         if m.split(".")[0] in outside]
    assert imported == []
