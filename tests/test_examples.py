"""Examples are part of the public API surface — smoke them in subprocesses
(each uses the installed package exactly as a user would)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", script), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_quickstart():
    p = _run("quickstart.py")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "recall@10" in p.stdout


def test_serve_ann():
    p = _run("serve_ann.py")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "achieved QPS" in p.stdout
    assert "recall@10" in p.stdout
