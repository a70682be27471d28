"""Native inference server: endpoint surface + runtime-launcher integration.

The endpoint surface is the one the reference's mock pins
(test/testdata/vllm-mock/mock_server.py: /health, /v1/models) plus real
/v1/completions; the integration test proves the agent's RuntimeServer
can spawn the native engine via RUNTIME_KIND=native with zero lifecycle
changes.
"""

from __future__ import annotations

import json
import re
import sys
import time
import urllib.error
import urllib.request

import jax
import pytest

from kubeinfer_tpu.agent.runtime import RuntimeConfig
from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference import server as server_mod
from kubeinfer_tpu.inference.engine import Engine
from kubeinfer_tpu.inference.server import InferenceServer

TINY = PRESETS["tiny"]


@pytest.fixture(scope="module")
def server():
    params = init_params(TINY, jax.random.PRNGKey(0))
    srv = InferenceServer(
        Engine(params, TINY), model_id="tiny-test", port=0
    ).start()
    yield srv
    srv.stop()


def get(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, json.loads(r.read() or b"null")


def post(url: str, body: dict):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class TestEndpoints:
    def test_health(self, server):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/health", timeout=10
        ) as r:
            assert r.read() == b"OK"  # mock_server.py:8-15 parity

    def test_models_list(self, server):
        status, body = get(f"http://127.0.0.1:{server.port}/v1/models")
        assert status == 200
        assert body["object"] == "list"
        assert body["data"][0]["id"] == "tiny-test"  # mock_server.py:17-29

    def test_completion_with_token_ids(self, server):
        status, body = post(
            f"http://127.0.0.1:{server.port}/v1/completions",
            {"prompt": [1, 2, 3, 4], "max_tokens": 4},
        )
        assert status == 200
        choice = body["choices"][0]
        assert len(choice["tokens"]) == 4
        assert body["usage"] == {
            "prompt_tokens": 4, "completion_tokens": 4, "total_tokens": 8,
        }
        # deterministic greedy: same request → same tokens
        _, body2 = post(
            f"http://127.0.0.1:{server.port}/v1/completions",
            {"prompt": [1, 2, 3, 4], "max_tokens": 4},
        )
        assert body2["choices"][0]["tokens"] == choice["tokens"]

    def test_string_prompt_without_tokenizer_rejected(self, server):
        status, body = post(
            f"http://127.0.0.1:{server.port}/v1/completions",
            {"prompt": "hello", "max_tokens": 2},
        )
        assert status == 400
        assert "tokenizer" in body["error"]["message"]

    def test_missing_prompt_rejected(self, server):
        status, _ = post(
            f"http://127.0.0.1:{server.port}/v1/completions", {"max_tokens": 2}
        )
        assert status == 400


class TestRuntimeLauncherIntegration:
    @pytest.mark.slow
    def test_runtime_kind_native_spawns_real_server(self, tmp_path, monkeypatch):
        """RUNTIME_KIND=native + the standard env contract boots the
        native engine as a subprocess through the unchanged RuntimeServer
        lifecycle (vllm.go Start/Stop parity)."""
        import socket

        from tests.conftest import subprocess_pythonpath

        monkeypatch.setenv("PYTHONPATH", subprocess_pythonpath())

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]

        cfg = RuntimeConfig.from_env({
            "RUNTIME_KIND": "native",
            "MODEL_PATH": "tiny",  # preset name + --random-init below
            "VLLM_HOST": "127.0.0.1",
            "VLLM_PORT": str(port),
            "VLLM_EXTRA_ARGS": "--random-init",
            "VLLM_DTYPE": "float32",
        })
        assert cfg.command_prefix[-1] == "kubeinfer_tpu.inference.server"

        from kubeinfer_tpu.agent.runtime import RuntimeServer

        srv = RuntimeServer(cfg)
        srv.start()
        try:
            deadline = time.monotonic() + 120
            up = False
            while time.monotonic() < deadline:
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health", timeout=2
                    ) as r:
                        up = r.read() == b"OK"
                        break
                except OSError:
                    time.sleep(0.5)
            assert up, "native runtime never became healthy"
            status, body = post(
                f"http://127.0.0.1:{port}/v1/completions",
                {"prompt": [5, 6, 7], "max_tokens": 3},
            )
            assert status == 200
            assert len(body["choices"][0]["tokens"]) == 3
        finally:
            srv.stop()
        assert not srv.running()

    def test_unknown_runtime_kind_rejected(self):
        with pytest.raises(ValueError, match="RUNTIME_KIND"):
            RuntimeConfig.from_env({"RUNTIME_KIND": "tgi"})


class TestDraftModelServing:
    """``--draft-model``: the draft runs inside the paged batch, so a
    request takes route ``continuous`` and keeps the tokens the same
    server gives without a draft."""

    @pytest.fixture(scope="class")
    def pair(self):
        from kubeinfer_tpu.inference.batching import ContinuousEngine

        params = init_params(TINY, jax.random.PRNGKey(0))
        dparams = init_params(TINY, jax.random.PRNGKey(1))
        engines, servers = [], []
        for spec_draft in (None, (dparams, TINY)):
            cont = ContinuousEngine(
                params, TINY, n_slots=2, cache_len=64, block_size=8,
                spec_draft=spec_draft, spec_k=3,
            ).start()
            engines.append(cont)
            servers.append(InferenceServer(
                Engine(params, TINY), model_id="tiny", port=0,
                continuous=cont,
            ))
        yield servers
        for cont in engines:
            cont.stop()

    @pytest.mark.parametrize("extra", [
        {},
        {"temperature": 0.8, "seed": 7, "top_k": 20},
        {"repetition_penalty": 1.3},
    ], ids=["greedy", "sampled", "penalised"])
    def test_tokens_match_the_draftless_server(self, pair, extra):
        plain, drafted = pair
        body = {"prompt": [5, 6, 7], "max_tokens": 8, **extra}
        want = plain.complete(dict(body))
        got = drafted.complete(dict(body))
        assert got["kubeinfer"]["route"] == "continuous"
        assert got["choices"][0]["tokens"] == want["choices"][0]["tokens"]

    def test_request_moves_the_draft_counters(self, pair):
        _, drafted = pair
        drafted.complete({"prompt": [2, 3, 4], "max_tokens": 6})
        drafted._refresh_spec_metrics()
        out = drafted.registry.render().replace("'", '"')
        assert 'route="continuous",outcome="ok"' in out
        line = next(ln for ln in out.splitlines()
                    if ln.startswith("kubeinfer_spec_draft_tokens_total "))
        assert float(line.split()[1]) > 0
        assert "kubeinfer_inference_spec" not in out


class TestDraftModelFlags:
    BASE = ["--model", "tiny", "--random-init", "--port", "0"]

    def test_draft_model_needs_the_batcher(self):
        with pytest.raises(SystemExit) as e:
            server_mod.main(
                [*self.BASE, "--draft-model", "tiny", "--batch-slots", "0"]
            )
        assert "--draft-model requires the continuous batcher" in str(
            e.value)

    @pytest.mark.parametrize("flag", [
        ["--speculative-draft"], ["--prewarm-spec", "1,2"],
    ], ids=["speculative-draft", "prewarm-spec"])
    def test_removed_flags_are_unknown(self, capsys, flag):
        with pytest.raises(SystemExit) as e:
            server_mod.main([*self.BASE, "--draft-model", "tiny", *flag])
        assert e.value.code == 2
        assert "unrecognized arguments: " + flag[0] in capsys.readouterr().err

    def test_help_lists_26_options(self, capsys):
        with pytest.raises(SystemExit):
            server_mod.main(["--help"])
        flags = set(re.findall(r"^  (--[a-z-]+)", capsys.readouterr().out,
                               flags=re.M))
        assert len(flags) == 26, sorted(flags)
        assert "--draft-model" in flags and "--speculation-depth" in flags


class TestServingMetrics:
    def test_metrics_endpoint_counts_requests(self, server):
        code, _ = post(
            f"http://127.0.0.1:{server.port}/v1/completions",
            {"prompt": [1, 2, 3], "max_tokens": 3},
        )
        assert code == 200
        import urllib.request

        with urllib.request.urlopen(
            f"http://127.0.0.1:{server.port}/metrics", timeout=10
        ) as r:
            body = r.read().decode()
        assert 'kubeinfer_inference_requests_total{route="engine",outcome="ok"}' in body
        assert "kubeinfer_inference_completion_tokens_total" in body
        assert "kubeinfer_inference_request_seconds_bucket" in body

    def test_invalid_requests_counted(self, server):
        before = server.metrics["requests"].value("invalid", "invalid")
        code, _ = post(
            f"http://127.0.0.1:{server.port}/v1/completions",
            {"prompt": [1], "max_tokens": 2, "top_p": 7.0},
        )
        assert code == 400
        assert server.metrics["requests"].value("invalid", "invalid") == before + 1

    def test_generation_errors_carry_route_label(self, server, monkeypatch):
        # an engine failure AFTER route selection must be counted under
        # that route, not the "invalid" sentinel (r2 review finding)
        def boom(*a, **kw):
            raise RuntimeError("engine exploded")

        monkeypatch.setattr(server.engine, "generate", boom)
        before = server.metrics["requests"].value("engine", "error")
        code, _ = post(
            f"http://127.0.0.1:{server.port}/v1/completions",
            {"prompt": [1, 2], "max_tokens": 2},
        )
        assert code == 500
        assert server.metrics["requests"].value("engine", "error") == before + 1

    def test_malformed_json_counted(self, server):
        import urllib.request

        before = server.metrics["requests"].value("invalid", "invalid")
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/v1/completions",
            data=b"{not json", method="POST",
            headers={"Content-Type": "application/json"},
        )
        try:
            urllib.request.urlopen(req, timeout=10)
            code = 200
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 400
        assert server.metrics["requests"].value("invalid", "invalid") == before + 1
