"""The rest of a run with the look for a chip skipped, on the CPU at the
rehearsal's size: a sound server comes out ``correct``, and one whose
answer has a token altered where it is produced does not. The other
faults a cell could have (a step that leaves its state unchanged, half
a batch left out, an exchange between chips left out) belong to
training and to several chips; a served model on one chip has this
one."""

import json

import pytest

import run as runner
from lib import client

CELL = "tiny-rehearsal.rehearsal"


def drive(capsys, seed):
    rc = runner.main(["--workload", CELL, "--seed", str(seed),
                      "--seconds", "6", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 0
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.fixture
def no_look_for_a_chip(monkeypatch):
    pytest.importorskip("jax")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(runner, "device_has_peaks", lambda device: True)


def test_a_sound_run_is_correct(no_look_for_a_chip, capsys):
    result, err = drive(capsys, 2**31 + 11)
    assert result["correct"] is True and result["failed"] == 0
    gap, limit = result["compared"]["logit_gap_max"]
    assert gap <= limit
    assert list(result)[-1] == "compared"
    # each number compared, beside its limit, ends standard error
    last = err.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") for line in last)
    assert f"logit_gap_max: {gap} (limit {limit})" in last[-1]


def test_an_altered_token_is_not_correct(no_look_for_a_chip, monkeypatch,
                                         capsys):
    real = client.http

    def altered(url, body=None, timeout=60.0):
        """The server's reply with the third token of every longer
        answer moved by one."""
        status, text = real(url, body, timeout)
        if body and body.get("max_tokens", 0) >= 4:
            doc = json.loads(text)
            toks = doc["choices"][0]["tokens"]
            toks[2] = (toks[2] + 1) % 256
            text = json.dumps(doc)
        return status, text

    monkeypatch.setattr(client, "http", altered)
    result, _ = drive(capsys, 2**31 + 12)
    gap, limit = result["compared"]["logit_gap_max"]
    assert gap > limit
    assert result["correct"] is False and result["failed"] == 0
