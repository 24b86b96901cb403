"""The guide's commands parse, and its documents name nothing that is gone.

The product of this repository is its READMEs. Every command in a guide
document that invokes one of the repository's entry points (a chapter's
``train_llm.py``, ``python -m distributed_training_guide_tpu.<module>``,
``chip_smoke.py``, ``benchmarks/run.py``, ``top-cluster.py``, a script beside
a README) is handed to that entry point's REAL parser, one case a (document,
command): an unknown flag or a removed choice fails the case. Nothing is run:
``parse_args`` is stopped as soon as the parser has accepted the arguments.

Commands are read from fenced blocks (backslash continuations joined) and
from inline code spans; a leading ``VAR=value`` environment and everything
from a shell operator on are dropped; a bare ``train_llm.py`` is the
document's own chapter's, or chapter 1's (the shared CLI) where the document
has none; ``<placeholder>``, ``$VAR`` and
``{field}`` stand for a value and become ``1``; the brackets of usage
notation (``[--zero1 | --zero2]``) are taken off; a command with ``...`` in
it, or with no argument at all (a mention in prose), is an abbreviation,
which may leave required arguments out but may not name a flag the parser
does not know.
"""
import argparse
import importlib
import importlib.util
import re
import shlex
import sys
from functools import lru_cache
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
PACKAGE = "distributed_training_guide_tpu"

DOCUMENTS = sorted(
    p.relative_to(REPO).as_posix() for p in (
        REPO / "README.md", REPO / "MIGRATING.md",
        REPO / ".claude/skills/verify/SKILL.md",
        *REPO.glob("[0-9][0-9]-*/README.md"),
        *(REPO / "alternative-frameworks").rglob("*.md"),
        *(REPO / "diagnosing-errors").rglob("*.md"),
        *(REPO / "related-topics").rglob("*.md")))

# files that PR 47 deleted: a document that still names one sends its reader
# to something that is not there
GONE = ("bench.py", "BENCH.md", "ops/overlap.py", "test_overlap.py",
        "test_bench_ladder.py")

# the reference's own command, quoted in the left column of the map
NOT_OURS = {("MIGRATING.md", "top-cluster.py", ("hosts",))}

# scripts that read their few arguments by hand: no parser to hand them to
BY_HAND = ("tests/onchip/kernel_parity.py", "tests/onchip/scope_events.py")
_HAS_MAIN = re.compile(r"^def main\(|^from \S+ import main$", re.M)

# ``python -m <package>`` runs the package's __main__, which for ``post``
# calls main() as it is imported: name the module that holds the parser
MAIN_MODULE = {f"{PACKAGE}.post": f"{PACKAGE}.post.cli",
               f"{PACKAGE}.serve": f"{PACKAGE}.serve.__main__"}

_FENCE = re.compile(r"^[ \t]*```[^\n]*\n(.*?)^[ \t]*```", re.S | re.M)
_SPAN = re.compile(r"`([^`]+)`")
_STANDS_FOR_A_VALUE = re.compile(r"<[^<>]*>|\$\{?\w+\}?|\{\w+\}")
_SHELL_OPERATORS = {"|", "||", "&", "&&", ";", ">", ">>", "<", ">&", "&>",
                    "|&", "(", ")"}


def _code_texts(markdown: str):
    """Candidate command lines: each logical line of a fenced block, and each
    inline code span with its line breaks folded."""
    for block in _FENCE.findall(markdown):
        yield from block.replace("\\\n", " ").splitlines()
    for span in _SPAN.findall(_FENCE.sub("", markdown)):
        yield " ".join(span.split())


def _tokens(line: str):
    line = _STANDS_FOR_A_VALUE.sub("1", line)    # before "<" reads as a redirect
    lex = shlex.shlex(line, posix=True, punctuation_chars=True)
    lex.whitespace_split = True
    lex.commenters = "#"
    try:
        return list(lex)
    except ValueError:        # an unbalanced quote: prose, not a command
        return []


def _python_commands(tokens):
    """Every ``python ...`` run in a token list, each cut at the next shell
    operator: a launcher's own command and the one after its ``--``."""
    for i, tok in enumerate(tokens):
        if tok in ("python", "python3"):
            rest = tokens[i + 1:]
            stop = next((j for j, t in enumerate(rest)
                         if t in _SHELL_OPERATORS), len(rest))
            if stop and rest[stop:stop + 1] == [">&"]:     # the 2 of 2>&1
                stop -= 1
            yield [t.strip("[]") for t in rest[:stop]]


def _entry_point(args, doc_dir: Path):
    """(kind, target, argv) for a command of this repository, else None."""
    if len(args) >= 2 and args[0] == "-m":
        if args[1].split(".")[0] != PACKAGE:
            return None
        return "module", args[1], args[2:]
    if not args or not args[0].endswith(".py"):
        return None
    places = [REPO / args[0], doc_dir / args[0]]
    if args[0] == "train_llm.py":
        places.append(REPO / "01-single-chip/train_llm.py")
    for path in places:
        path = path.resolve()
        if path.is_file() and path.is_relative_to(REPO):
            rel = path.relative_to(REPO).as_posix()
            if rel not in BY_HAND and (rel == "benchmarks/run.py"
                                       or _HAS_MAIN.search(path.read_text())):
                return "script", rel, args[1:]
    return None


def _collect():
    cases = []
    for doc in DOCUMENTS:
        seen = set()
        for line in _code_texts((REPO / doc).read_text()):
            for args in _python_commands(_tokens(line)):
                found = _entry_point(args, (REPO / doc).parent)
                if found is None:
                    continue
                kind, target, argv = found
                elided = "..." in argv or not argv
                argv = tuple(a for a in argv if a != "...")
                if (target, argv) not in seen and \
                        (doc, target, argv) not in NOT_OURS:
                    seen.add((target, argv))
                    cases.append(pytest.param(
                        kind, target, argv, elided,
                        id=f"{doc}:{target.removeprefix(PACKAGE + '.')} "
                           f"{' '.join(argv)}"[:120]))
    return cases


CASES = _collect()


class _Parsed(BaseException):
    """Out of ``parse_args`` once the real parser has taken the arguments (a
    BaseException: ``launch.errors.record`` must not write an error file)."""


@lru_cache(maxsize=None)
def _main_of(kind: str, target: str):
    if kind == "module":
        return importlib.import_module(MAIN_MODULE.get(target, target)).main
    if target == "benchmarks/run.py":
        from benchmarks import harness
        return lambda: harness.main(sys.argv[1:], t_process_start=0.0)
    name = "guide_" + re.sub(r"\W", "_", target)
    spec = importlib.util.spec_from_file_location(name, REPO / target)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.main


def test_the_documents_hold_commands():
    """The collector still finds the guide's commands (an extractor that
    finds nothing would pass every case it does not make)."""
    assert len(CASES) >= 30
    assert len(DOCUMENTS) >= 27


@pytest.mark.parametrize("kind,target,argv,elided", CASES)
def test_command_parses(kind, target, argv, elided, monkeypatch, capsys):
    real = argparse.ArgumentParser.parse_args

    def parse_then_stop(self, args=None, namespace=None):
        raise _Parsed(real(self, args, namespace))

    main = _main_of(kind, target)
    monkeypatch.setattr(argparse.ArgumentParser, "parse_args", parse_then_stop)
    monkeypatch.setattr(sys, "argv", [target, *argv])
    try:
        main()
    except _Parsed:
        return
    except SystemExit:
        said = capsys.readouterr().err
        if elided and "arguments are required" in said:
            return
        pytest.fail(f"{target} refuses `{' '.join(argv)}`:\n{said}")
    pytest.fail(f"{target}: main() returned without parsing its arguments")


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_nothing_that_is_gone(doc):
    text = (REPO / doc).read_text()
    assert [name for name in GONE if name in text] == []
