"""The tree is what its documents say it is: one LLM serving path, and no
document that describes the tree names a file the tree does not have."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# documents that describe something else: the round's inputs and the seed's
# baseline (the upstream project's paths), the driver's own files, and the
# log, whose entries name files as they were when each was written
NOT_ABOUT_THE_TREE = {
    "SURVEY.md", "PAPER.md", "PAPERS.md", "SNIPPETS.md", "BASELINE.md",
    "ISSUE.md", "REVIEW.md", "CHANGES.md",
}
# files a run writes, and a placeholder: named in documents, never in the tree
NOT_REPO_FILES = {
    "detail.json",  # benchmarks/run.py, one a run, under benchmarks/out/
    "head_meta.json",  # the head, into its session directory
    "a.py",  # benchmarks/README.md: "a name `a.b` is read by `a.py`"
}
# where a document's relative path may start
BASES = ("", "ray_tpu", "benchmarks")
PATH = re.compile(r"[\w.-]+(?:/[\w.-]+)*\.(?:py|json|md)")


def _tree_files():
    found = []
    for top, dirs, files in os.walk(REPO):
        # generated and ignored directories hold copies of other trees
        dirs[:] = [
            d for d in dirs
            if d == ".claude" or not (d.startswith(".") or d in ("chiprun_out", "out", "__pycache__"))
        ]
        found += [os.path.relpath(os.path.join(top, f), REPO) for f in files]
    return found


TREE_FILES = frozenset(_tree_files())
DOCUMENTS = sorted(f for f in TREE_FILES if f.endswith(".md") and f not in NOT_ABOUT_THE_TREE)


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_docs_name_no_missing_file(doc):
    """Every path in backticks that ends in .py, .json or .md is a file of
    the tree: as written, under ``ray_tpu/`` or ``benchmarks/``, beside the
    document, or (a bare or partial path) the tail of some file's path.  A
    ``:line`` suffix and what follows the path's first word are ignored;
    absolute paths and patterns (``<run>``, ``*``) are not paths of the tree."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    missing = set()
    for quoted in re.findall(r"`([^`\n]+)`", text):
        name = re.sub(r":\d[\d,:\s-]*$", "", quoted.split()[0])
        if not PATH.fullmatch(name) or name in NOT_REPO_FILES:
            continue
        here = os.path.dirname(doc)
        if any(os.path.normpath(os.path.join(base, name)) in TREE_FILES for base in BASES + (here,)):
            continue
        if not any(path.endswith("/" + name) for path in TREE_FILES):
            missing.add(name)
    assert not missing, f"{doc} names files the tree does not have: {sorted(missing)}"


@pytest.mark.parametrize(
    "module, owner, gone",
    [
        ("ray_tpu.serve.llm", None, "engine_llm_deployment".removeprefix("engine_")),
        ("ray_tpu.serve.llm", "ShardedLLM", "generate"),
        ("ray_tpu.models.llama", "LlamaModel", "decode_step"),
        ("ray_tpu.models.llama", "LlamaModel", "init_cache"),
    ],
)
def test_one_llm_path(module, owner, gone):
    """The static-batch deployment and its dense-cache forward are gone:
    what serves an LLM is ``engine_llm_deployment`` over the paged programs,
    and what checks them is the plain forward (tests/_greedy.py)."""
    import importlib

    mod = importlib.import_module(module)
    assert not hasattr(getattr(mod, owner) if owner else mod, gone)
    if owner is None:
        assert gone not in mod.__all__ and "engine_llm_deployment" in mod.__all__


def test_the_layer_apply_scans_takes_no_cache():
    import inspect

    from ray_tpu.models.llama import LlamaModel

    assert list(inspect.signature(LlamaModel._layer).parameters) == ["self", "x", "lp", "positions", "mesh"]
