"""Disaggregated prefill/decode: the KV transfer plane end to end.

Layering mirrors the subsystem: wire-format tests are pure numpy
(encode/decode/corruption — every torn-stream mode must surface as
WireError before any page reaches a pool), export-cache tests are pure
LRU bookkeeping, engine tests drive the REAL export capture and import
scatter (the load-bearing checks: an imported prefix must make the
decode token stream byte-identical to a cold local prefill, greedy AND
sampled — the import installs only pool/trie state, so any drift means
the scattered pages differ from what prefill would have written), and
the HTTP/router tests stand up real servers for the two-phase route.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from kubeinfer_tpu.disagg.client import (
    KVFetchError,
    fetch_kv_blocks,
    import_remote_prefix,
)
from kubeinfer_tpu.disagg.export import KVExportCache
from kubeinfer_tpu.disagg.wire import (
    KVBlockPayload,
    WireError,
    decode_payload,
    encode_payload,
)
from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference.batching import ContinuousEngine
from kubeinfer_tpu.inference.engine import Engine
from kubeinfer_tpu.inference.kv_blocks import prefix_fingerprints
from kubeinfer_tpu.inference.server import InferenceServer
from kubeinfer_tpu.router import FleetRouter, RouterServer

TINY = PRESETS["tiny"]
BS = 16  # block size shared by every engine here


@pytest.fixture(scope="module")
def params():
    return init_params(TINY, jax.random.PRNGKey(0))


def mk_engine(params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 128)
    kw.setdefault("block_size", BS)
    return ContinuousEngine(params, TINY, **kw).start()


def prompt_tokens(n, seed=11):
    rng = np.random.default_rng(seed)
    return rng.integers(0, TINY.vocab_size, size=n).tolist()


def _pages(blocks=3, layers=2, n_kv=2, d=8, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    shape = (layers, blocks, 4, n_kv, d)
    k = rng.standard_normal(shape).astype(dtype)
    v = rng.standard_normal(shape).astype(dtype)
    return k, v


class TestWire:
    def test_round_trip_float32(self):
        k, v = _pages()
        fps = [10, 20, 30]
        blob = encode_payload(k, v, fps, block_size=4)
        p = decode_payload(blob)
        assert isinstance(p, KVBlockPayload)
        assert np.array_equal(p.pages_k, k)
        assert np.array_equal(p.pages_v, v)
        assert p.fingerprints == (10, 20, 30)
        assert p.block_size == 4
        assert p.blocks == 3
        assert p.byte_size == k.nbytes + v.nbytes

    def test_round_trip_bfloat16(self):
        ml_dtypes = pytest.importorskip("ml_dtypes")
        k, v = _pages(dtype=ml_dtypes.bfloat16)
        blob = encode_payload(k, v, [1, 2, 3], block_size=4)
        p = decode_payload(blob)
        assert p.pages_k.dtype == np.dtype(ml_dtypes.bfloat16)
        assert np.array_equal(p.pages_k, k)

    def test_body_corruption_fails_checksum(self):
        k, v = _pages()
        blob = bytearray(encode_payload(k, v, [1, 2, 3], block_size=4))
        blob[-10] ^= 0x01  # one flipped bit deep in the V pages
        with pytest.raises(WireError, match="checksum"):
            decode_payload(bytes(blob))

    def test_truncated_body_detected_before_checksum(self):
        k, v = _pages()
        blob = encode_payload(k, v, [1, 2, 3], block_size=4)
        with pytest.raises(WireError, match="truncated"):
            decode_payload(blob[:-5])

    def test_bad_magic_and_missing_header(self):
        with pytest.raises(WireError):
            decode_payload(b'{"magic": "nope"}\nxxxx')
        with pytest.raises(WireError, match="header"):
            decode_payload(b"no newline anywhere")

    def test_encode_validates_shape_agreement(self):
        k, v = _pages()
        with pytest.raises(WireError, match="fingerprints"):
            encode_payload(k, v, [1, 2], block_size=4)  # 3 blocks
        with pytest.raises(WireError, match="disagree"):
            encode_payload(k, v[:, :2], [1, 2, 3], block_size=4)
        with pytest.raises(WireError, match="layers"):
            encode_payload(k[0], v[0], [1, 2, 3], block_size=4)

    def test_round_trip_v2_int8(self):
        k, v = _pages(dtype=np.int8)
        rng = np.random.default_rng(3)
        sk = rng.random((2, 3, 2)).astype(np.float32)
        sv = rng.random((2, 3, 2)).astype(np.float32)
        blob = encode_payload(k, v, [1, 2, 3], block_size=4,
                              scales_k=sk, scales_v=sv, kv_dtype="int8")
        assert blob.split(b"\n", 1)[0].startswith(
            b'{"magic": "kubeinfer-kvwire/2"'
        )
        p = decode_payload(blob)
        assert p.kv_dtype == "int8"
        assert np.array_equal(p.pages_k, k)
        assert np.array_equal(p.scales_k, sk)
        assert np.array_equal(p.scales_v, sv)
        assert p.byte_size == k.nbytes + v.nbytes + sk.nbytes + sv.nbytes

    def test_bf16_export_stays_v1_byte_identical(self):
        # a pre-quantization fleet must see the exact v1 bytes it
        # always did — the v2 magic appears only when scales do
        k, v = _pages()
        blob = encode_payload(k, v, [1, 2, 3], block_size=4)
        assert blob.split(b"\n", 1)[0].startswith(
            b'{"magic": "kubeinfer-kvwire/1"'
        )
        assert b"kv_dtype" not in blob.split(b"\n", 1)[0]
        p = decode_payload(blob)
        assert p.kv_dtype == "bf16" and p.scales_k is None

    def test_v2_scale_corruption_fails_checksum(self):
        k, v = _pages(dtype=np.int8)
        sk = np.ones((2, 3, 2), np.float32)
        blob = bytearray(encode_payload(
            k, v, [1, 2, 3], block_size=4,
            scales_k=sk, scales_v=sk, kv_dtype="int8",
        ))
        blob[-3] ^= 0x10  # deep in the V scales
        with pytest.raises(WireError, match="checksum"):
            decode_payload(bytes(blob))

    def test_encode_validates_dtype_scale_agreement(self):
        k, v = _pages(dtype=np.int8)
        sk = np.ones((2, 3, 2), np.float32)
        with pytest.raises(WireError, match="together"):
            encode_payload(k, v, [1, 2, 3], block_size=4, scales_k=sk,
                           kv_dtype="int8")
        with pytest.raises(WireError, match="inconsistent"):
            encode_payload(k, v, [1, 2, 3], block_size=4,
                           kv_dtype="int8")
        with pytest.raises(WireError, match="inconsistent"):
            encode_payload(k, v, [1, 2, 3], block_size=4,
                           scales_k=sk, scales_v=sk)
        with pytest.raises(WireError, match="float32"):
            encode_payload(k, v, [1, 2, 3], block_size=4,
                           scales_k=sk.astype(np.float64),
                           scales_v=sk, kv_dtype="int8")

    def test_v2_header_claiming_bf16_rejected(self):
        # a forged v2 header downgrading kv_dtype would make the body
        # length check pass against phantom scale bytes — refuse it at
        # the header parse
        k, v = _pages(dtype=np.int8)
        sk = np.ones((2, 3, 2), np.float32)
        blob = encode_payload(k, v, [1, 2, 3], block_size=4,
                              scales_k=sk, scales_v=sk, kv_dtype="int8")
        nl = blob.find(b"\n")
        hdr = json.loads(blob[:nl])
        hdr["kv_dtype"] = "bf16"
        with pytest.raises(WireError, match="bf16"):
            decode_payload(json.dumps(hdr).encode() + blob[nl:])

    def test_header_shape_inconsistency_detected(self):
        # a header claiming a different block count than its body
        # implies must fail on the implied-size check, not reshape junk
        k, v = _pages()
        blob = encode_payload(k, v, [1, 2, 3], block_size=4)
        nl = blob.find(b"\n")
        hdr = json.loads(blob[:nl])
        hdr["blocks"] = 2
        hdr["fingerprints"] = [1, 2]
        forged = json.dumps(hdr).encode() + blob[nl:]
        with pytest.raises(WireError):
            decode_payload(forged)


class TestExportCache:
    def test_lru_eviction_and_touch(self):
        c = KVExportCache(capacity=2)
        c.put(1, b"one")
        c.put(2, b"two")
        assert c.get(1) == b"one"  # touches 1: now 2 is LRU-oldest
        c.put(3, b"three")
        assert c.get(2) is None
        assert c.get(1) == b"one" and c.get(3) == b"three"
        s = c.stats()
        assert s["evictions"] == 1 and s["entries"] == 2
        assert s["hits"] == 3 and s["misses"] == 1

    def test_put_same_key_replaces_without_eviction(self):
        c = KVExportCache(capacity=2)
        c.put(1, b"a")
        c.put(1, b"b")
        assert len(c) == 1 and c.get(1) == b"b"
        assert c.stats()["evictions"] == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            KVExportCache(capacity=0)


class TestEngineImport:
    def test_prefill_only_export_capture(self, params):
        eng = mk_engine(params)
        try:
            p = prompt_tokens(70)
            req = eng.serve(p, max_new_tokens=0, eos_id=-1,
                            export_kv=True)
            # prefill-only retires with zero generated tokens but a
            # captured export of every full prompt block
            assert req.out_tokens == []
            exp = req.kv_export
            assert exp is not None
            assert exp["block_size"] == BS
            assert exp["fingerprints"] == prefix_fingerprints(p, BS)
            n = len(p) // BS
            assert exp["pages_k"].shape[1] == n
            assert exp["pages_k"].shape == exp["pages_v"].shape
            # capture must not leak the walk's references: all export
            # blocks are trie-held only (evictable) afterwards
            assert eng.kv_cache_stats()["blocks_in_use"] == n
        finally:
            eng.stop()

    def test_no_export_without_flag_or_full_block(self, params):
        eng = mk_engine(params)
        try:
            req = eng.serve(prompt_tokens(40), max_new_tokens=0,
                            eos_id=-1)
            assert req.kv_export is None  # flag off
            req = eng.serve(prompt_tokens(BS - 1, seed=5),
                            max_new_tokens=0, eos_id=-1, export_kv=True)
            assert req.kv_export is None  # no full block to export
        finally:
            eng.stop()

    def test_import_parity_greedy_and_sampled(self, params):
        """THE disaggregation contract: decode over imported blocks is
        byte-identical to decode over a local cold prefill."""
        p = prompt_tokens(70)
        ref = mk_engine(params)
        ref_g = ref.generate(p, max_new_tokens=6, eos_id=-1)
        ref_s = ref.generate(p, max_new_tokens=6, eos_id=-1,
                             temperature=0.8, seed=123)
        ref.stop()

        a = mk_engine(params)
        exp = a.serve(p, max_new_tokens=0, eos_id=-1,
                      export_kv=True).kv_export
        a.stop()
        payload = decode_payload(encode_payload(
            exp["pages_k"], exp["pages_v"], exp["fingerprints"],
            exp["block_size"],
        ))

        b = mk_engine(params)
        try:
            fps = prefix_fingerprints(p, BS)
            n, reason = b.import_prefix(
                p[:len(fps) * BS], payload.pages_k, payload.pages_v,
            )
            assert (n, reason) == (len(fps), None)
            assert b.imports_total == 1
            assert b.imported_blocks_total == len(fps)
            # the decode side recomputes at least the final prompt
            # token (committed-blocks rule) but NO imported block
            hits_before = b.kv_cache_stats()["hits"]
            assert b.generate(p, max_new_tokens=6, eos_id=-1) == ref_g
            assert b.kv_cache_stats()["hits"] == hits_before + 1
            assert b.generate(p, max_new_tokens=6, eos_id=-1,
                              temperature=0.8, seed=123) == ref_s
        finally:
            b.stop()

    def test_wire_is_token_major_whatever_the_pool_stores(self, params):
        """Pages leave a pool of known contents as the token-major
        ``[layers, blocks, block_size, n_kv, D]`` arrays the wire has
        always carried, byte for byte, and come back the same through
        an import: the stored (head-major) layout ends at the engine's
        edge, so no peer and no wire version can tell."""
        import dataclasses

        import jax.numpy as jnp

        from kubeinfer_tpu.inference.batching import _ImportTask
        from kubeinfer_tpu.inference.kv_blocks import (
            pool_shape,
            rows_to_pages,
        )

        # never started: this thread is _state's only reader and writer
        eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=128,
                               block_size=BS)
        L, nkv, D = (TINY.num_hidden_layers, TINY.num_key_value_heads,
                     TINY.head_dim)
        pool = eng._state.caches_k[0]
        nb = pool.shape[0]
        assert pool.shape == pool_shape(nb, BS, nkv, D)
        # every element names its logical address (layer, block, token,
        # head, dim): token-major, as the parent's pool held it and put
        # it on the wire
        known_k = np.arange(L * nb * BS * nkv * D, dtype=np.float32) \
            .reshape(L, nb, BS, nkv, D).astype(pool.dtype)
        known_v = -known_k
        eng._state = dataclasses.replace(
            eng._state,
            caches_k=[jnp.asarray(rows_to_pages(x)) for x in known_k],
            caches_v=[jnp.asarray(rows_to_pages(x)) for x in known_v],
        )
        blocks = [5, 2, 7]
        pk, pv = eng._export_pages(jnp.asarray(blocks, jnp.int32))
        for got, known in ((pk, known_k), (pv, known_v)):
            want = np.ascontiguousarray(known[:, blocks])
            assert got.shape == (L, len(blocks), BS, nkv, D)
            assert got.flags["C_CONTIGUOUS"]
            assert got.tobytes() == want.tobytes()
        toks = prompt_tokens(len(blocks) * BS)
        fps = prefix_fingerprints(toks, BS)
        blob = encode_payload(pk, pv, fps, BS)
        assert blob == encode_payload(
            known_k[:, blocks], known_v[:, blocks], fps, BS)

        # import -> export: the blocks land wherever the pool has room
        # and read back as the bytes that came in
        payload = decode_payload(blob)
        eng._imports.append(
            _ImportTask(toks, payload.pages_k, payload.pages_v))
        eng._step_import()
        landed = eng._radix.match(toks)
        eng._pool.unref(landed)
        assert len(landed) == len(blocks)
        rk, rv = eng._export_pages(jnp.asarray(landed, jnp.int32))
        assert rk.tobytes() == pk.tobytes()
        assert rv.tobytes() == pv.tobytes()
        assert encode_payload(rk, rv, fps, BS) == blob

    def test_duplicate_import_dedups(self, params):
        p = prompt_tokens(70)
        a = mk_engine(params)
        exp = a.serve(p, max_new_tokens=0, eos_id=-1,
                      export_kv=True).kv_export
        a.stop()
        b = mk_engine(params)
        try:
            fps = prefix_fingerprints(p, BS)
            toks = p[:len(fps) * BS]
            for _ in range(2):
                n, reason = b.import_prefix(
                    toks, exp["pages_k"], exp["pages_v"],
                )
                assert (n, reason) == (len(fps), None)
            # second import found every node cached: its fresh blocks
            # freed right back, so occupancy is one copy, not two
            assert b.kv_cache_stats()["blocks_in_use"] == len(fps)
        finally:
            b.stop()

    def test_import_rejects_bad_shapes(self, params):
        eng = mk_engine(params)
        try:
            k, v = _pages(blocks=2, layers=2, n_kv=2, d=8)
            # wrong page geometry for this engine
            n, reason = eng.import_prefix(list(range(2 * BS)), k, v)
            assert n == 0 and reason == "shape_mismatch"
            # token count disagreeing with block count
            exp_shape = (TINY.num_hidden_layers, 1, BS,
                         TINY.num_key_value_heads, TINY.head_dim)
            kk = np.zeros(exp_shape, np.float32)
            n, reason = eng.import_prefix(list(range(3)), kk, kk)
            assert n == 0 and reason == "shape_mismatch"
        finally:
            eng.stop()

    def test_int8_export_import_parity(self, params):
        """The disaggregation contract under quantization: decode over
        imported int8 pages + scales is token-identical to the int8
        engine's own cold prefill (NOT to bf16 — the int8 path is
        tolerance-pinned against bf16, but exact against itself)."""
        p = prompt_tokens(70)
        ref = mk_engine(params, kv_dtype="int8")
        ref_g = ref.generate(p, max_new_tokens=6, eos_id=-1)
        ref.stop()

        a = mk_engine(params, kv_dtype="int8")
        exp = a.serve(p, max_new_tokens=0, eos_id=-1,
                      export_kv=True).kv_export
        a.stop()
        assert exp["kv_dtype"] == "int8"
        assert exp["pages_k"].dtype == np.int8
        payload = decode_payload(encode_payload(
            exp["pages_k"], exp["pages_v"], exp["fingerprints"],
            exp["block_size"], scales_k=exp["scales_k"],
            scales_v=exp["scales_v"], kv_dtype="int8",
        ))

        b = mk_engine(params, kv_dtype="int8")
        try:
            fps = prefix_fingerprints(p, BS)
            n, reason = b.import_prefix(
                p[:len(fps) * BS], payload.pages_k, payload.pages_v,
                scales_k=payload.scales_k, scales_v=payload.scales_v,
                kv_dtype="int8",
            )
            assert (n, reason) == (len(fps), None)
            assert b.generate(p, max_new_tokens=6, eos_id=-1) == ref_g
        finally:
            b.stop()

    def test_import_rejects_kv_dtype_mismatch(self, params):
        # both directions: a bf16 blob must not scatter into an int8
        # pool (its pages would be reinterpreted as quantized) and an
        # int8 blob must not scatter into a bf16 pool
        p = list(range(BS))
        int8_eng = mk_engine(params, kv_dtype="int8")
        try:
            exp_shape = (TINY.num_hidden_layers, 1, BS,
                         TINY.num_key_value_heads, TINY.head_dim)
            kk = np.zeros(exp_shape, np.float32)
            n, reason = int8_eng.import_prefix(p, kk, kk)
            assert (n, reason) == (0, "kv_dtype_mismatch")
        finally:
            int8_eng.stop()
        bf16_eng = mk_engine(params)
        try:
            exp_shape = (TINY.num_hidden_layers, 1, BS,
                         TINY.num_key_value_heads, TINY.head_dim)
            kq = np.zeros(exp_shape, np.int8)
            sc = np.ones((TINY.num_hidden_layers, 1,
                          TINY.num_key_value_heads), np.float32)
            n, reason = bf16_eng.import_prefix(
                p, kq, kq, scales_k=sc, scales_v=sc, kv_dtype="int8",
            )
            assert (n, reason) == (0, "kv_dtype_mismatch")
        finally:
            bf16_eng.stop()


class TestClient:
    def test_fetch_unreachable_is_fetch_error(self, params):
        eng = mk_engine(params)
        try:
            n, reason, _ = import_remote_prefix(
                eng, prompt_tokens(40), "http://127.0.0.1:9",
                timeout_s=0.5,
            )
            assert n == 0 and reason == "fetch_error"
            with pytest.raises(KVFetchError):
                fetch_kv_blocks("http://127.0.0.1:9", 1, timeout_s=0.5)
        finally:
            eng.stop()

    def test_sub_block_prompt_short_circuits(self, params):
        eng = mk_engine(params)
        try:
            n, reason, nbytes = import_remote_prefix(
                eng, prompt_tokens(BS - 1), "http://127.0.0.1:9",
            )
            assert (n, reason, nbytes) == (0, "no_full_block", 0)
        finally:
            eng.stop()

    def test_wire_v1_blob_rejected_by_int8_importer(self, params,
                                                    monkeypatch):
        """Mixed-fleet regression: a pre-quantization (wire v1, bf16)
        prefill replica answering an int8 decode replica must degrade
        to local prefill with the kv_dtype_mismatch fallback reason —
        never scatter bf16 bytes into the quantized pool, and never
        misreport the valid v1 blob as a wire error."""
        p = prompt_tokens(70)
        a = mk_engine(params)  # bf16 exporter -> v1 on the wire
        exp = a.serve(p, max_new_tokens=0, eos_id=-1,
                      export_kv=True).kv_export
        a.stop()
        blob = encode_payload(exp["pages_k"], exp["pages_v"],
                              exp["fingerprints"], exp["block_size"])
        assert blob.split(b"\n", 1)[0].startswith(
            b'{"magic": "kubeinfer-kvwire/1"'
        )

        import kubeinfer_tpu.disagg.client as client_mod

        monkeypatch.setattr(
            client_mod, "fetch_kv_blocks",
            lambda *a, **kw: decode_payload(blob),
        )
        eng = mk_engine(params, kv_dtype="int8")
        try:
            n, reason, nbytes = import_remote_prefix(
                eng, p, "http://unused",
            )
            assert (n, reason) == (0, "kv_dtype_mismatch")
            assert nbytes > 0  # the blob was fetched and decoded fine
            assert eng.imports_total == 0  # never reached the engine
        finally:
            eng.stop()


@pytest.mark.slow
class TestServerEndpoints:
    @pytest.fixture(scope="class")
    def fleet(self, params):
        servers = []
        for name in ("pre", "dec"):
            cont = mk_engine(params)
            srv = InferenceServer(
                Engine(params, TINY), model_id=name, port=0,
                continuous=cont,
            ).start()
            servers.append((srv, cont))
        yield servers
        for srv, cont in servers:
            srv.stop()
            cont.stop()

    def _post(self, port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read())

    def test_prefill_only_then_kv_blocks_fetch(self, fleet):
        (pre, pre_cont), _ = fleet
        p = prompt_tokens(70, seed=21)
        status, doc = self._post(pre.port, {
            "prompt": p, "max_tokens": 0,
        })
        assert status == 200
        assert doc["kubeinfer"]["route"] == "prefill"
        assert doc["usage"]["completion_tokens"] == 0
        ext = doc["kubeinfer"]["kv_export"]
        fps = prefix_fingerprints(p, BS)
        assert ext["fingerprint"] == fps[-1]
        assert ext["blocks"] == len(fps)
        # the wire blob round-trips through the endpoint
        payload = fetch_kv_blocks(
            f"http://127.0.0.1:{pre.port}", fps[-1],
        )
        assert list(payload.fingerprints) == fps
        # export-direction metrics materialized
        with urllib.request.urlopen(
            f"http://127.0.0.1:{pre.port}/metrics", timeout=10
        ) as r:
            body = r.read().decode()
        assert 'kubeinfer_kv_stream_blocks_total{direction="export"}' \
            in body

    def test_kv_blocks_miss_and_bad_query(self, fleet):
        (pre, _), _ = fleet
        for q, code in (("fp=424242", 404), ("fp=wat", 400), ("", 400)):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{pre.port}/kv/blocks?{q}",
                    timeout=10,
                )
            assert ei.value.code == code

    def test_kv_source_hook_imports_and_serves_parity(self, fleet,
                                                      params):
        (pre, _), (dec, dec_cont) = fleet
        p = prompt_tokens(70, seed=22)
        ref = mk_engine(params)
        expect = ref.generate(p, max_new_tokens=5, eos_id=-1)
        ref.stop()
        self._post(pre.port, {"prompt": p, "max_tokens": 0})
        imports_before = dec_cont.imports_total
        status, doc = self._post(dec.port, {
            "prompt": p, "max_tokens": 5,
            "kubeinfer_kv_source": f"http://127.0.0.1:{pre.port}",
        })
        assert status == 200
        assert doc["choices"][0]["tokens"] == expect
        assert dec_cont.imports_total == imports_before + 1
        # a locally-warm repeat must skip the fetch entirely
        status, doc = self._post(dec.port, {
            "prompt": p, "max_tokens": 5,
            "kubeinfer_kv_source": f"http://127.0.0.1:{pre.port}",
        })
        assert doc["choices"][0]["tokens"] == expect
        assert dec_cont.imports_total == imports_before + 1

    def test_kv_source_unreachable_falls_back_locally(self, fleet,
                                                      params):
        _, (dec, dec_cont) = fleet
        p = prompt_tokens(70, seed=23)
        ref = mk_engine(params)
        expect = ref.generate(p, max_new_tokens=4, eos_id=-1)
        ref.stop()
        status, doc = self._post(dec.port, {
            "prompt": p, "max_tokens": 4,
            "kubeinfer_kv_source": "http://127.0.0.1:9",
        })
        assert status == 200
        assert doc["choices"][0]["tokens"] == expect
        assert dec.metrics["disagg_fallbacks"].value("fetch_error") > 0

    def test_stale_export_fingerprint_chain_guard(self, fleet, params):
        """A stale/colliding export must be rejected by the full-chain
        compare, never scattered: plant a blob for OTHER tokens under
        OUR deepest fingerprint and watch the import refuse it."""
        (pre, _), _ = fleet
        ours = prompt_tokens(70, seed=24)
        theirs = prompt_tokens(70, seed=25)
        a = mk_engine(params)
        exp = a.serve(theirs, max_new_tokens=0, eos_id=-1,
                      export_kv=True).kv_export
        a.stop()
        blob = encode_payload(exp["pages_k"], exp["pages_v"],
                              exp["fingerprints"], exp["block_size"])
        our_fps = prefix_fingerprints(ours, BS)
        pre.kv_exports.put(our_fps[-1], blob)
        b = mk_engine(params)
        try:
            n, reason, _ = import_remote_prefix(
                b, ours, f"http://127.0.0.1:{pre.port}",
            )
            assert n == 0 and reason == "fingerprint_mismatch"
            assert b.imports_total == 0
        finally:
            b.stop()


@pytest.mark.slow
class TestRouterTwoPhase:
    def test_two_phase_route_is_token_identical(self, params):
        p = prompt_tokens(70, seed=31)
        short = prompt_tokens(20, seed=32)
        ref = mk_engine(params)
        expect = ref.generate(p, max_new_tokens=5, eos_id=-1)
        expect_s = ref.generate(p, max_new_tokens=5, eos_id=-1,
                                temperature=0.7, seed=9)
        expect_short = ref.generate(short, max_new_tokens=3, eos_id=-1)
        ref.stop()

        servers = {}
        for name in ("prefill0", "decode0", "decode1"):
            cont = mk_engine(params)
            srv = InferenceServer(
                Engine(params, TINY), model_id=name, port=0,
                continuous=cont,
            ).start()
            servers[name] = (srv, cont)
        router = FleetRouter()
        for name in ("decode0", "decode1"):
            router.add_replica(
                name, f"http://127.0.0.1:{servers[name][0].port}")
        router.add_prefill_replica(
            "prefill0", f"http://127.0.0.1:{servers['prefill0'][0].port}")
        rs = RouterServer(router, port=0, prefill_threshold=64)
        rs.poll_once()
        rs.start(poll=False)
        try:
            def forward(body):
                code, payload = rs.forward(json.dumps(body).encode())
                return code, json.loads(payload)

            code, doc = forward({"prompt": p, "max_tokens": 5})
            assert code == 200
            assert doc["choices"][0]["tokens"] == expect
            # the prefill tier did the prefill; exactly one decode
            # replica imported the blocks
            assert len(servers["prefill0"][0].kv_exports) >= 1
            imports = sum(servers[n][1].imports_total
                          for n in ("decode0", "decode1"))
            assert imports == 1
            assert router.metrics["prefill_routed"].value("prefill0") \
                == 1

            # sampled rides the same plane, same identity
            code, doc = forward({"prompt": p, "max_tokens": 5,
                                 "temperature": 0.7, "seed": 9})
            assert code == 200
            assert doc["choices"][0]["tokens"] == expect_s

            # short prompts bypass the prefill tier entirely
            before = router.metrics["prefill_routed"].value("prefill0")
            code, doc = forward({"prompt": short, "max_tokens": 3})
            assert code == 200
            assert doc["choices"][0]["tokens"] == expect_short
            assert router.metrics["prefill_routed"].value("prefill0") \
                == before

            # the prefill tier never served a completion
            for outcome in ("ok",):
                assert router.metrics["requests"].value(
                    "prefill0", outcome) == 0
            snap = rs.replica_snapshot()
            assert {v["name"]: v["role"] for v in snap} == {
                "decode0": "decode", "decode1": "decode",
                "prefill0": "prefill",
            }
        finally:
            rs.stop()
            for srv, cont in servers.values():
                srv.stop()
                cont.stop()
