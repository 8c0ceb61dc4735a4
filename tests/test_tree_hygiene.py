"""No command in the tree names a file that is gone, and no file names
a module or a switch that left.

A deletion leaves its readers behind where nothing executes them in
tier-1: the image's `COPY` lines, the shell gates, the commands the
verify skill hands the next builder, the scripts beastlint's
FLAG-PARITY groups compare. Each is read here as text and every file it
names must be in the checkout.
"""

import fnmatch
import functools
import glob
import os
import re
import subprocess

import pytest

from torchbeast_tpu.analysis import config as lint_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKILL = os.path.join(".claude", "skills", "verify", "SKILL.md")

# A path below a directory of sources, or a script at the root; not
# what a build leaves (`build/`), a glob or a shell variable.
_NAMED_FILE = re.compile(
    r"(?<![\w/.${}*-])"
    r"((?:scripts|benchmarks|tests|torchbeast_tpu|perfbench|csrc)"
    r"/[\w./-]*\w\.(?:py|sh|cc|h|json)|\w+\.py)"
    r"(?![\w*${])"
)
_MODULE_RUN = re.compile(r"\bpython3? -m ([\w.]+)")
# `python ...`, `python3 ...`, `bash ...`, behind variables and the
# chip tool's own options.
_COMMAND = re.compile(
    r"(?:[A-Z_]+=\S+ )*(?:chiprun (?:--\w+ \S+ )*-- )?(?:python3?|bash) "
)


def _read(path):
    with open(os.path.join(REPO, path)) as f:
        return f.read()


def _module_file(module):
    """Where `python -m module` finds its code, below the checkout."""
    base = module.replace(".", os.sep)
    return base + ".py" if os.path.isfile(
        os.path.join(REPO, base + ".py")
    ) else os.path.join(base, "__main__.py")


def _named_in(text):
    return _NAMED_FILE.findall(text) + [
        _module_file(module) for module in _MODULE_RUN.findall(text)
    ]


def _dockerfile():
    for line in _read("Dockerfile").splitlines():
        if line.startswith("COPY "):
            yield from ((line, src) for src in line.split()[1:-1])


def _shell_scripts():
    for name in sorted(glob.glob("scripts/*.sh", root_dir=REPO)):
        yield from ((name, named) for named in _named_in(_read(name)))


def _skill_commands():
    """The backticked commands; a line break inside one is a space, so
    a path is written unbroken."""
    for span in re.findall(r"`([^`]+)`", _read(SKILL)):
        command = re.sub(r"\s+", " ", span.strip())
        if _COMMAND.match(command):
            yield from ((command, named) for named in _named_in(command))


def _flag_parity_groups():
    for group in lint_config.FLAG_PARITY_GROUPS:
        yield from ((group, path) for path in group)


SOURCES = {
    "dockerfile-copy": _dockerfile,
    "shell-scripts": _shell_scripts,
    "verify-skill": _skill_commands,
    "flag-parity-groups": _flag_parity_groups,
}


@pytest.mark.parametrize("source", sorted(SOURCES))
def test_every_command_names_a_file_that_exists(source):
    named = list(SOURCES[source]())
    assert named, "nothing was read"
    gone = [
        (where, path) for where, path in named
        if not os.path.exists(os.path.join(REPO, path))
    ]
    assert not gone, gone


# The four kernels from before the chip and their switches (PR 70), by
# module and by option: a sentence that documents one documents nothing.
# Built in two parts so that this file does not name them either.
LEFT = [
    "pallas_" + module
    for module in ("attention", "opt", "pool", "vtrace", "smoke")
] + ["--" + flag + "_impl" for flag in ("opt", "attention", "vtrace")] + [
    "TBT_POOL_" + "PALLAS"
]
# The histories, whole files: what a PR did is said there in the names
# of its day. The driver writes the last two kinds at the root.
HISTORIES = (
    "CHANGES.md", "ISSUE.md", "REVIEW.md", "PERF.md", "ROADMAP.md",
    "PERF_LEDGER.jsonl", "BENCH_r*.json", "MULTICHIP_r*.json",
)


@functools.lru_cache(maxsize=None)
def _tracked():
    """The files git would commit: `git ls-files`, or where the checkout
    is no repository, the walk less what `.gitignore` lists."""
    listed = subprocess.run(
        ["git", "ls-files"], cwd=REPO, capture_output=True, text=True
    )
    if listed.returncode == 0 and listed.stdout:
        return listed.stdout.splitlines()
    ignored = [
        line.strip().rstrip("/") for line in _read(".gitignore").splitlines()
        if line.strip() and not line.startswith("#")
    ] + [".git"]

    def kept(name):
        return not any(fnmatch.fnmatch(name, pattern) for pattern in ignored)

    files = []
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if kept(d)]
        files += [
            os.path.relpath(os.path.join(root, name), REPO)
            for name in names if kept(name)
        ]
    return files


@pytest.mark.parametrize("name", LEFT)
def test_no_tracked_file_names_what_left(name):
    naming = []
    for path in _tracked():
        if any(fnmatch.fnmatch(path, history) for history in HISTORIES):
            continue
        if name in path:
            naming.append(path)
            continue
        try:
            text = _read(path)
        except (UnicodeDecodeError, FileNotFoundError):
            continue  # not text; deleted and not yet committed
        if name in text:
            naming.append(path)
    assert not naming, naming
