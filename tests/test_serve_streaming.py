"""Serve streaming: generator replica methods stream chunks through the
handle, the HTTP proxy (SSE/chunked), and the gRPC ingress — the LLM
token-decode serving pattern (reference: serve/_private/proxy.py:896,975
streaming HTTP + gRPC proxies; handle.py DeploymentResponseGenerator).

The load-bearing assertions are TIMING ones: the first chunk must arrive
while the producer is still sleeping between later chunks — proving
streaming, not buffer-then-flush.
"""
from __future__ import annotations

import json
import time
import urllib.request

import pytest

from conftest import serve_http_url, shutdown_if_setup_fails

N_CHUNKS = 4
CHUNK_GAP_S = 0.8
# first chunk must land at least this long before the stream completes;
# the producer tail after chunk 1 is (N_CHUNKS - 1) * CHUNK_GAP_S = 2.4s
MIN_STREAM_SPREAD_S = 1.0


@pytest.fixture(scope="module")
def streaming_cluster():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=6)
    with shutdown_if_setup_fails():
        serve.start(http_options={"port": 0}, grpc_options={"port": 0})

        @serve.deployment
        class Decoder:
            """Fake LLM decode loop: one token per CHUNK_GAP_S."""

            def __call__(self, payload):
                prompt = (payload or {}).get("prompt", "")
                for i in range(N_CHUNKS):
                    yield {"token": f"{prompt}-{i}"}
                    if i < N_CHUNKS - 1:
                        time.sleep(CHUNK_GAP_S)

            def plain(self, payload):
                return {"done": True, "payload": payload}

        serve.run(Decoder.bind(), name="stream_app", route_prefix="/decode",
                  timeout_s=180)
    yield ray_tpu, serve
    serve.shutdown()
    ray_tpu.shutdown()


def _assert_streamed(t_first: float, t_all: float) -> None:
    assert t_all - t_first > MIN_STREAM_SPREAD_S, (
        f"chunks arrived in a burst (first at {t_first:.2f}s, last at "
        f"{t_all:.2f}s) — response was buffered, not streamed"
    )


# ---------------------------------------------------------------- core

def test_actor_generator_method_streams(streaming_cluster):
    """Substrate check: plain actor generator methods stream refs out
    before the method finishes (num_returns='streaming' on actor tasks)."""
    ray_tpu, _ = streaming_cluster

    @ray_tpu.remote
    class Gen:
        def produce(self, n):
            for i in range(n):
                yield i * 10
                time.sleep(CHUNK_GAP_S)

    g = Gen.remote()
    t0 = time.monotonic()
    gen = g.produce.options(num_returns="streaming").remote(4)
    first = ray_tpu.get(next(gen), timeout=120)
    t_first = time.monotonic() - t0
    rest = [ray_tpu.get(r, timeout=120) for r in gen]
    t_all = time.monotonic() - t0
    assert first == 0 and rest == [10, 20, 30]
    _assert_streamed(t_first, t_all)


def test_actor_generator_error_propagates(streaming_cluster):
    ray_tpu, _ = streaming_cluster

    @ray_tpu.remote
    class Bad:
        def produce(self):
            yield 1
            raise ValueError("boom mid-stream")

    b = Bad.remote()
    gen = b.produce.options(num_returns="streaming").remote()
    assert ray_tpu.get(next(gen), timeout=120) == 1
    with pytest.raises(Exception, match="boom mid-stream"):
        for r in gen:
            ray_tpu.get(r, timeout=120)


# ---------------------------------------------------------------- handle

def test_handle_streams_chunks_incrementally(streaming_cluster):
    _, serve = streaming_cluster
    handle = serve.get_app_handle("stream_app")
    t0 = time.monotonic()
    response = handle.remote({"prompt": "tok"})
    from ray_tpu.serve import DeploymentResponseGenerator

    assert isinstance(response, DeploymentResponseGenerator)
    chunks = []
    t_first = None
    for chunk in response:
        if t_first is None:
            t_first = time.monotonic() - t0
        chunks.append(chunk)
    t_all = time.monotonic() - t0
    assert [c["token"] for c in chunks] == [f"tok-{i}" for i in range(N_CHUNKS)]
    _assert_streamed(t_first, t_all)


def test_non_generator_method_still_unary(streaming_cluster):
    _, serve = streaming_cluster
    handle = serve.get_app_handle("stream_app")
    out = handle.plain.remote({"x": 1}).result(timeout=120)
    assert out == {"done": True, "payload": {"x": 1}}


# ---------------------------------------------------------------- HTTP

def test_http_proxy_streams_sse(streaming_cluster):
    req = urllib.request.Request(
        serve_http_url("/decode"),
        data=json.dumps({"prompt": "sse"}).encode(),
        headers={"Content-Type": "application/json",
                 "Accept": "text/event-stream"},
    )
    t0 = time.monotonic()
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        events = []
        t_first = None
        for raw in resp:
            line = raw.decode().strip()
            if line.startswith("data: "):
                if t_first is None:
                    t_first = time.monotonic() - t0
                events.append(json.loads(line[len("data: "):]))
    t_all = time.monotonic() - t0
    assert [e["token"] for e in events] == [f"sse-{i}" for i in range(N_CHUNKS)]
    _assert_streamed(t_first, t_all)


def test_http_proxy_streams_chunked_json(streaming_cluster):
    """Without an SSE Accept header the proxy streams newline-delimited
    JSON chunks over chunked transfer encoding."""
    req = urllib.request.Request(
        serve_http_url("/decode"),
        data=json.dumps({"prompt": "nd"}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        chunks = [json.loads(ln) for ln in resp if ln.strip()]
    assert [c["token"] for c in chunks] == [f"nd-{i}" for i in range(N_CHUNKS)]


# ---------------------------------------------------------------- gRPC

def _grpc_channel(serve):
    import grpc

    port = serve.grpc_port()
    assert port, "gRPC proxy did not report a bound port"
    return grpc.insecure_channel(f"127.0.0.1:{port}")


def test_grpc_ingress_streaming(streaming_cluster):
    _, serve = streaming_cluster
    ch = _grpc_channel(serve)
    stream = ch.unary_stream(
        "/ray_tpu.serve.ServeAPI/Stream",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    t0 = time.monotonic()
    chunks = []
    t_first = None
    for raw in stream(json.dumps({"prompt": "g"}).encode(),
                      metadata=(("application", "stream_app"),),
                      timeout=120):
        if t_first is None:
            t_first = time.monotonic() - t0
        chunks.append(json.loads(raw)["result"])
    t_all = time.monotonic() - t0
    ch.close()
    assert [c["token"] for c in chunks] == [f"g-{i}" for i in range(N_CHUNKS)]
    _assert_streamed(t_first, t_all)


def test_grpc_ingress_unary_and_errors(streaming_cluster):
    import grpc

    _, serve = streaming_cluster
    ch = _grpc_channel(serve)
    call = ch.unary_unary(
        "/ray_tpu.serve.ServeAPI/Call",
        request_serializer=lambda b: b,
        response_deserializer=lambda b: b,
    )
    # unary call on a non-generator method via metadata routing
    out = json.loads(call(
        json.dumps({"y": 2}).encode(),
        metadata=(("application", "stream_app"), ("method", "plain")),
        timeout=120,
    ))
    assert out["result"] == {"done": True, "payload": {"y": 2}}
    # unknown application -> NOT_FOUND
    with pytest.raises(grpc.RpcError) as exc_info:
        call(b"{}", metadata=(("application", "nope"),), timeout=120)
    assert exc_info.value.code() == grpc.StatusCode.NOT_FOUND
    ch.close()
