"""`serve` entry end-to-end (api.serve_text -> serving/serve.py): the shipped
configs/config_serve.yaml drives YAML -> component graph -> ServingEngine ->
JSONL result rows, with fresh-init params (checkpoint_folder_path: null)."""

import json

import pytest
import yaml

CFG = "configs/config_serve.yaml"


def _byte_tokenizer_dir(dst):
    from tests.conftest import make_word_level_tokenizer

    vocab = {f"t{i}": i for i in range(256)}
    vocab["<eod>"] = 255
    del vocab["t255"]
    make_word_level_tokenizer(vocab, dst, unk_token="t0", pad_token="t0", eos_token="<eod>")


@pytest.fixture(scope="module")
def served_rows(tmp_path_factory):
    from pathlib import Path

    from modalities_tpu.api import serve_text

    workdir = tmp_path_factory.mktemp("serve_cli")
    _byte_tokenizer_dir(workdir / "tokenizer")
    cfg = yaml.safe_load(Path(CFG).read_text())
    cfg["serving_component"]["config"]["tokenizer"]["config"][
        "pretrained_model_name_or_path"
    ] = str(workdir / "tokenizer")
    cfg["serving_component"]["config"]["max_batch_slots"] = 2
    # halve the depth (the shipped config's wiring is what's under test, not its
    # exact size; widths are already at the validator's floor of 128) — keeps
    # the compile out of the tier-1 budget
    cfg["serving_component"]["config"]["model"]["config"]["n_layer"] = 1
    # the shipped objectives stay armed, their sampler's first tick an hour away: the first request's TTFT is its compile,
    # and where the replay took over the shipped 5 s (a busy machine) the tick read 5.5 s against `p99 < 0.5`, the brownout
    # shed the queued third request, and its row said "shed" (PR 47's run; docs/known_failures.md)
    cfg["serving_component"]["config"]["slo"]["sample_interval_s"] = 3600.0
    cfg_path = workdir / "config_serve.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))

    requests = [
        {"prompt": "t5 t6 t7", "max_new_tokens": 6},
        {"prompt": "t9 t10", "max_new_tokens": 4, "temperature": 0.8, "seed": 3},
        {"prompt": "t1", "max_new_tokens": 3},
    ]
    req_path = workdir / "requests.jsonl"
    req_path.write_text("\n".join(json.dumps(r) for r in requests) + "\n")
    out_path = workdir / "results.jsonl"
    serve_text(cfg_path, requests_file_path=req_path, output_file_path=out_path)
    return [json.loads(line) for line in out_path.read_text().splitlines() if line.strip()]


def test_serve_cli_replays_jsonl_requests(served_rows):
    assert len(served_rows) == 3
    for row in served_rows:
        for key in ("rid", "prompt", "completion", "tokens", "finish_reason", "ttft_s", "latency_s"):
            assert key in row, (key, sorted(row))
        assert row["finish_reason"] in ("eod", "budget", "capacity")
        assert row["latency_s"] >= row["ttft_s"] >= 0.0


def test_serve_cli_completions_decode_to_known_vocab(served_rows):
    for row in served_rows:
        assert len(row["tokens"]) <= {0: 6, 1: 4, 2: 3}[row["rid"]]
        for tok in row["completion"].split():
            assert tok.startswith("t") or tok == "<eod>", row["completion"]


@pytest.mark.slow  # subprocess CLI + compile + real SIGTERM drain (~1-2 min CPU)
def test_serve_cli_http_end_to_end_with_sigterm_drain(tmp_path):
    """Full `python -m modalities_tpu serve --http_port` lifecycle: the server
    comes up, streams one SSE generation, and a real SIGTERM drains it to
    exit code 0 (the resilience flag-only handler, not a hard kill)."""
    import http.client
    import os
    import signal
    import socket
    import subprocess
    import sys
    import time
    from pathlib import Path

    _byte_tokenizer_dir(tmp_path / "tokenizer")
    cfg = yaml.safe_load(Path(CFG).read_text())
    scfg = cfg["serving_component"]["config"]
    scfg["tokenizer"]["config"]["pretrained_model_name_or_path"] = str(tmp_path / "tokenizer")
    scfg["max_batch_slots"] = 2
    scfg["model"]["config"]["n_layer"] = 1
    scfg["kv_cache"] = "paged"  # serving v2 path end to end
    cfg_path = tmp_path / "config_serve.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))

    with socket.socket() as s:  # free ephemeral port (benign bind race)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    proc = subprocess.Popen(
        [sys.executable, "-m", "modalities_tpu", "serve",
         "--config_file_path", str(cfg_path), "--http_port", str(port)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        deadline = time.monotonic() + 240
        while True:  # healthz poll: imports + engine construction dominate
            assert proc.poll() is None, proc.communicate(timeout=30)[1][-3000:]
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
                conn.request("GET", "/healthz")
                up = conn.getresponse().status == 200
                conn.close()
                if up:
                    break
            except OSError:
                time.sleep(1.0)
            assert time.monotonic() < deadline, "serve --http_port never came up"

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": "t5 t6 t7", "max_new_tokens": 4}),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Content-Type").startswith("text/event-stream")
        payload = resp.read().decode()  # Connection: close bounds the stream
        conn.close()
        events = [json.loads(b[len("data: "):]) for b in payload.split("\n\n")
                  if b.startswith("data: ")]
        done = [e for e in events if e.get("done")]
        assert len(done) == 1
        assert done[0]["finish_reason"] in ("eod", "budget")
        assert [e["token_id"] for e in events if "token_id" in e] == done[0]["token_ids"]

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=90) == 0  # graceful drain, not a crash
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
