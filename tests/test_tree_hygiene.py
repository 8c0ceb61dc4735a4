"""No command in the tree names a file that is gone.

A deletion leaves its readers behind where nothing executes them in
tier-1: the image's `COPY` lines, the shell gates, the commands the
verify skill hands the next builder, the scripts beastlint's
FLAG-PARITY groups compare. Each is read here as text and every file it
names must be in the checkout.
"""

import glob
import os
import re

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
