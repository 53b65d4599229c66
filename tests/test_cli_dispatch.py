"""CLI dispatcher contract: exit codes 0/1/141 + typo suggestions.

The exit codes are the scriptability surface (`goleft-tpu X && ...`):
0 for help/version, 1 for unknown subcommands and bad input, 141
(128+SIGPIPE) when downstream closes the pipe — pinned here so a
dispatcher refactor can't silently change them.
"""

import ast
import functools
import os

import numpy as np
import pytest

import goleft_tpu
from goleft_tpu.cli import PROGS, main as cli_main
from helpers import write_bam_and_bai


def test_help_and_version_exit_zero(capsys):
    assert cli_main([]) == 0
    assert "depth" in capsys.readouterr().err
    assert cli_main(["--help"]) == 0
    assert cli_main(["--version"]) == 0


def test_unknown_subcommand_suggests_close_match(capsys):
    assert cli_main(["dept"]) == 1
    err = capsys.readouterr().err
    assert "unknown subcommand: dept" in err
    assert "did you mean depth?" in err
    # a suggestion replaces the table dump
    assert "matricize" not in err


def test_unknown_subcommand_far_from_any_prints_table(capsys):
    assert cli_main(["qqzzxy"]) == 1
    err = capsys.readouterr().err
    assert "unknown subcommand: qqzzxy" in err
    # no plausible guess: the full sorted table prints instead
    for name in PROGS:
        assert name in err


def test_serve_is_registered():
    assert "serve" in PROGS
    assert PROGS["serve"][2] is True  # device command: warm bring-up


_PKG = os.path.dirname(os.path.abspath(goleft_tpu.__file__))


def _resolve(parts):
    """(path of the module or of the package's __init__, is_package)
    for a dotted name under the checkout's goleft_tpu, or None."""
    base = os.path.join(os.path.dirname(_PKG), *parts)
    if os.path.isfile(os.path.join(base, "__init__.py")):
        return os.path.join(base, "__init__.py"), True
    return (base + ".py", False) if os.path.isfile(base + ".py") else None


def _package_files(top):
    for root, _, files in os.walk(top):
        yield from (os.path.join(root, f) for f in files
                    if f.endswith(".py"))


def test_every_command_lives_inside_the_package():
    """Every module PROGS names is a file of the package, and nothing
    in the package reaches into the checkout around it through
    ``sys.path``: the CLI has to work from an installed wheel."""
    for name, (_, runner, _) in PROGS.items():
        module = runner.__closure__[0].cell_contents
        assert module.startswith("."), (name, module)
        assert _resolve(["goleft_tpu"] + module[1:].split(".")), (
            name, module)
    for path in _package_files(_PKG):
        assert "sys.path" not in open(path).read(), path


def test_a_removed_command_answers_like_any_unknown_one(capsys):
    """``bench`` has no row and no special case: the benchmark is
    benchmark/run.py, outside the package."""
    assert "bench" not in PROGS
    assert cli_main(["bench"]) == 1
    assert "unknown subcommand: bench" in capsys.readouterr().err


def _bound_names(body):
    """Names a module binds at its top level (through if/try/with)."""
    out = set()
    for node in body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            out.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            out.update((a.asname or a.name).split(".")[0]
                       for a in node.names)
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = getattr(node, "targets", None) or [node.target]
            out.update(n.id for t in targets for n in ast.walk(t)
                       if isinstance(n, ast.Name))
        else:
            for field in ("body", "orelse", "finalbody", "handlers"):
                out |= _bound_names(getattr(node, field, []))
    return out


@functools.lru_cache(maxsize=None)
def _module_names(path):
    return _bound_names(ast.parse(open(path).read()).body)


@pytest.mark.parametrize("sub", [""] + sorted(
    d for d in os.listdir(_PKG)
    if os.path.isfile(os.path.join(_PKG, d, "__init__.py"))))
def test_intra_package_imports_resolve(sub):
    """Every ``from .x import y`` of the package, the lazy ones inside
    functions included, names a module that exists and a name it binds:
    what a deleted module leaves behind in code no test calls (a smoke's
    leg, a fallback branch) fails here and not on the chip."""
    top = os.path.join(_PKG, sub)
    files = [os.path.join(top, f) for f in os.listdir(top)
             if f.endswith(".py")]
    assert files
    for path in files:
        here = os.path.relpath(path, os.path.dirname(_PKG))[:-3].split(
            os.sep)
        for node in ast.walk(ast.parse(open(path).read())):
            if not isinstance(node, ast.ImportFrom):
                continue
            mod = node.module.split(".") if node.module else []
            if node.level:
                mod = here[:len(here) - node.level] + mod
            elif mod[:1] != ["goleft_tpu"]:
                continue
            where = (os.path.relpath(path, _PKG), node.lineno)
            found = _resolve(mod)
            assert found, (where, ".".join(mod))
            src, is_pkg = found
            names = _module_names(src)
            for a in node.names:
                assert (a.name == "*" or a.name in names
                        or "__getattr__" in names
                        or (is_pkg and _resolve(mod + [a.name]))), (
                    where, ".".join(mod), a.name)


def test_broken_pipe_exits_141(tmp_path, monkeypatch, capsys):
    """`goleft-tpu samplename x.bam | head -c0` analog: stdout's pipe
    is closed, the tool must die silently with 141."""
    rng = np.random.default_rng(0)
    bam = str(tmp_path / "t.bam")
    write_bam_and_bai(bam, [(0, int(s), "50M", 60, 0)
                            for s in sorted(rng.integers(0, 900, 20))],
                      ref_names=("chr1",), ref_lens=(1000,),
                      header_text="@HD\tVN:1.6\tSO:coordinate\n"
                                  "@SQ\tSN:chr1\tLN:1000\n"
                                  "@RG\tID:r\tSM:s1\n")

    class _ClosedPipe:
        def write(self, *_):
            raise BrokenPipeError(32, "Broken pipe")

        def flush(self):
            pass

    monkeypatch.setattr("sys.stdout", _ClosedPipe())
    rc = cli_main(["samplename", bam])
    assert rc == 141
    err = capsys.readouterr().err
    assert "Traceback" not in err
