"""Native inference server: endpoint surface + runtime-launcher integration.

The endpoint surface is the one the reference's mock pins
(test/testdata/vllm-mock/mock_server.py: /health, /v1/models) plus real
/v1/completions; the integration test proves the agent's RuntimeServer
can spawn the native engine via RUNTIME_KIND=native with zero lifecycle
changes.
"""

from __future__ import annotations

import json
import sys
import time
import urllib.error
import urllib.request

import jax
import pytest

from kubeinfer_tpu.agent.runtime import RuntimeConfig
from kubeinfer_tpu.inference import PRESETS, init_params
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


class TestSpeculativeServing:
    @pytest.fixture(scope="class")
    def spec_server(self):
        from kubeinfer_tpu.inference.speculative import SpeculativeEngine

        params = init_params(TINY, jax.random.PRNGKey(0))
        engine = Engine(params, TINY)
        # self-draft: acceptance 1.0, output must equal vanilla greedy
        spec = SpeculativeEngine(params, TINY, params, TINY, k=3)
        srv = InferenceServer(
            engine, model_id="tiny-spec", port=0, speculative=spec
        ).start()
        yield srv, engine
        srv.stop()

    def test_greedy_request_routes_through_speculation(self, spec_server):
        srv, engine = spec_server
        body = {"prompt": [5, 6, 7], "max_tokens": 8, "temperature": 0.0}
        code, resp = post(
            f"http://127.0.0.1:{srv.port}/v1/completions", body
        )
        assert code == 200
        ref = engine.generate([[5, 6, 7]], max_new_tokens=8)
        assert resp["choices"][0]["tokens"] == ref.tokens[0].tolist()
        # the speculative path actually ran (stats recorded)
        assert srv.speculative.last_stats["rounds"] >= 1

    def test_sampled_request_takes_speculation(self, spec_server):
        """Sampled requests ride the draft too since r3's rejection-
        sampling correction (speculative.py) — only repetition-penalty
        requests still skip it."""
        srv, _ = spec_server
        srv.speculative.last_stats = None
        body = {
            "prompt": [5, 6, 7], "max_tokens": 4,
            "temperature": 0.8, "seed": 7,
        }
        code, resp = post(
            f"http://127.0.0.1:{srv.port}/v1/completions", body
        )
        assert code == 200
        assert len(resp["choices"][0]["tokens"]) >= 1
        assert srv.speculative.last_stats is not None  # path taken

    def test_repetition_penalty_skips_speculation(self, spec_server):
        srv, _ = spec_server
        srv.speculative.last_stats = None
        body = {
            "prompt": [5, 6, 7], "max_tokens": 4,
            "repetition_penalty": 1.3,
        }
        code, resp = post(
            f"http://127.0.0.1:{srv.port}/v1/completions", body
        )
        assert code == 200
        assert len(resp["choices"][0]["tokens"]) >= 1
        assert srv.speculative.last_stats is None  # path not taken


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


class TestBatcherOwnsDraftTraffic:
    def test_eligible_requests_route_to_batcher_groups(self):
        """With a batcher configured, draft-eligible requests route
        'continuous' and ride the batcher's incremental spec groups
        (visible in the spec gauges) — the serialized bulk 'speculative'
        route remains only for batcher-less servers (r4 verdict item 5:
        speculation must survive load, and the batcher is where load
        lives)."""
        from kubeinfer_tpu.inference.batching import ContinuousEngine
        from kubeinfer_tpu.inference.server import InferenceServer
        from kubeinfer_tpu.inference.speculative import SpeculativeEngine

        cfg = PRESETS["tiny"]
        params = init_params(cfg, jax.random.PRNGKey(0))
        spec = SpeculativeEngine(params, cfg, params, cfg, k=2)
        cont = ContinuousEngine(
            params, cfg, n_slots=2, cache_len=256, speculative=spec
        ).start()
        srv = InferenceServer(
            Engine(params, cfg), model_id="tiny", port=0,
            continuous=cont, speculative=spec,
        )
        try:
            resp = srv.complete({"prompt": [5, 6, 7], "max_tokens": 5})
            assert resp["usage"]["completion_tokens"] == 5
            m = srv.registry.render().replace("'", '"')
            assert 'route="continuous",outcome="ok"' in m
            assert 'route="speculative"' not in m
            srv._refresh_spec_metrics()
            out = srv.registry.render()
            assert "spec_served_requests 1" in out, out.splitlines()[-4:]
        finally:
            cont.stop()
