"""Driver-entry tests: the multichip dry run executes on the devices this
process has, and says so when there are too few — it never goes looking
for another backend."""

import os
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import __graft_entry__ as graft  # noqa: E402


def test_dryrun_multichip_runs_on_local_devices(capsys):
    graft.dryrun_multichip(1)
    out = capsys.readouterr().out
    assert "llama train step OK on 1 devices" in out
    assert "all multichip checks passed" in out


def test_dryrun_multichip_refuses_too_few_devices():
    with pytest.raises(RuntimeError, match="need .* devices, found"):
        graft.dryrun_multichip(len(jax.devices()) + 1)
