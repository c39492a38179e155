"""The documents name what exists.

One case a document: every ``.py``/``.sh`` path it names is a file of this
repo, and every ``--flag`` it names is an option that some parser of this
repo declares (``python -m gaussiank_sgd_tpu.train`` first among them). A
file or an option that a PR deletes leaves the documents in the same PR.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKIP_DIRS = {".git", "ci_checkout", "chiprun_out", "runs", "__pycache__",
             ".jax_cache", ".pytest_cache", "build"}

DOCS = ["README.md", "BASELINE.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "*.md"))) + [
    ".claude/skills/verify/SKILL.md"]

# the reference's files (sb17v/GaussianK-SGD, SURVEY.md) and jax's own
NOT_OURS = {"compression.py", "hv_distributed_optimizer.py",
            "allreducer.py", "dl_trainer.py", "horovod_trainer.py",
            "tpu_info.py",
            # placeholders in docs/LINTING.md's examples
            "mod.py", "file.py"}

# options of tools the documents run that are not this repo's parsers
FOREIGN_FLAGS = {
    # pytest, mypy, scripts/check.sh's own
    "--continue-on-collection-errors", "--dist", "--config-file",
    "--no-tests",
    # the chip tool
    "--chips", "--timeout", "--status",
    # XLA
    "--xla_force_host_platform_device_count",
}

PATH = re.compile(r"(?<![\w/.-])([\w./-]*\w\.(?:py|sh))\b")
FLAG = re.compile(r"(?<![\w-])(--[a-z][a-z0-9_-]*)")
DECLARED = re.compile(r"""add_argument\(\s*["'](--[a-z][a-z0-9_-]*)["']"""
                      r"""(?:\s*,\s*["'](--[a-z][a-z0-9_-]*)["'])?""")


@pytest.fixture(scope="module")
def repo_files():
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        out.extend(os.path.relpath(os.path.join(root, f), REPO)
                   .replace(os.sep, "/") for f in files)
    return out


@pytest.fixture(scope="module")
def declared_flags(repo_files):
    flags = set(FOREIGN_FLAGS)
    for rel in repo_files:
        if not rel.endswith(".py") or rel.startswith("tests/"):
            continue
        with open(os.path.join(REPO, rel), encoding="utf-8") as fh:
            src = fh.read()
        for m in DECLARED.finditer(src):
            for flag in filter(None, m.groups()):
                flags.add(flag)
                # argparse.BooleanOptionalAction declares the negation
                flags.add("--no-" + flag[2:])
    return flags


@pytest.mark.parametrize("doc", DOCS)
def test_a_document_names_files_and_options_that_exist(doc, repo_files,
                                                       declared_flags):
    with open(os.path.join(REPO, doc), encoding="utf-8") as fh:
        text = fh.read()
    missing = sorted(
        p for p in {m.group(1).lstrip("./") for m in PATH.finditer(text)}
        if os.path.basename(p) not in NOT_OURS
        and not any(f == p or f.endswith("/" + p) for f in repo_files))
    unknown = sorted({m.group(1) for m in FLAG.finditer(text)}
                     - declared_flags)
    assert not missing and not unknown, (
        f"{doc} names files that do not exist: {missing}; "
        f"options no parser has: {unknown}")
