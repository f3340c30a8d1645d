"""Train→serve fleet deployment loop, end to end (slow): a real
`python -m modalities_tpu serve --fleet` subprocess on the shipped
configs/config_fleet.yaml, watching a real checkpoint ring on disk.

The full story in one process lifetime:
1. the fleet BOOTS from the newest sealed ring checkpoint (watcher bootstrap);
2. a newly sealed GOOD checkpoint is canary-deployed and PROMOTED to every
   worker (generation 1 on the whole fleet) while requests keep flowing;
3. a POISONED (NaN) checkpoint seals next: the canary takes it, its requests
   error, and the rollout ROLLS BACK during probation — the bad generation
   never reaches the full fleet and the donor generation keeps serving;
4. SIGTERM drains the router + workers to a clean exit 0.
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest
import yaml

CFG = "configs/config_fleet.yaml"

pytestmark = pytest.mark.slow  # subprocess + 2 engine compiles + probation windows


def _save_ring_step(ring, step, params):
    import jax.numpy as jnp
    import orbax.checkpoint as ocp

    from modalities_tpu.resilience.manifest import write_manifest

    folder = ring / f"eid_0-seen_steps_{step}"
    tree = {
        "params": params,
        "opt_state": {"count": jnp.zeros((), jnp.int32)},
        "step": jnp.asarray(step, dtype=jnp.int32),
    }
    checkpointer = ocp.StandardCheckpointer()
    checkpointer.save(folder.absolute(), tree)
    checkpointer.wait_until_finished()
    write_manifest(folder)  # seal only after the commit, like the trainer
    return folder


def _get_json(port, path, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        return resp.status, json.loads(body)
    finally:
        conn.close()


def _post_generate(port, prompt, timeout=120.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(
            "POST", "/generate",
            body=json.dumps({"prompt": prompt, "max_new_tokens": 4}),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        payload = resp.read().decode()
        events = [
            json.loads(b[len("data: "):])
            for b in payload.split("\n\n")
            if b.startswith("data: ")
        ]
        return resp.status, events
    finally:
        conn.close()


def test_fleet_train_to_serve_loop_with_canary_rollback(tmp_path):
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from tests.conftest import make_word_level_tokenizer
    from tests.models.test_gpt2_model import tiny_gpt2

    # ---- tokenizer + config: the shipped fleet config, shrunk to 1 layer
    vocab = {f"t{i}": i for i in range(256)}
    vocab["<eod>"] = 255
    del vocab["t255"]
    make_word_level_tokenizer(
        vocab, tmp_path / "tokenizer", unk_token="t0", pad_token="t0", eos_token="<eod>"
    )
    ring = tmp_path / "ring"
    ring.mkdir()

    cfg = yaml.safe_load(Path(CFG).read_text())
    scfg = cfg["serving_component"]["config"]
    scfg["tokenizer"]["config"]["pretrained_model_name_or_path"] = str(tmp_path / "tokenizer")
    scfg["model"]["config"]["n_layer"] = 1
    scfg["max_batch_slots"] = 2
    scfg["watch_ring_path"] = str(ring)
    scfg["watch_poll_s"] = 0.5
    scfg["probation_s"] = 2.0
    scfg["probation_tick_s"] = 0.1
    scfg["health_interval_s"] = 0.2
    cfg_path = tmp_path / "config_fleet.yaml"
    cfg_path.write_text(yaml.safe_dump(cfg))

    # ---- the "training" side: a model of the config's architecture
    model = tiny_gpt2(
        "pytorch_flash", vocab_size=256, sequence_length=64, n_layer=1
    )
    params0 = meta.unbox(model.init_params(jax.random.PRNGKey(0)))
    params1 = meta.unbox(model.init_params(jax.random.PRNGKey(1)))
    poisoned = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), params0)
    _save_ring_step(ring, 10, params0)  # the boot generation

    with socket.socket() as s:  # free ephemeral port (benign bind race)
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    proc = subprocess.Popen(
        [sys.executable, "-m", "modalities_tpu", "serve", "--fleet",
         "--config_file_path", str(cfg_path), "--http_port", str(port)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    try:
        # ---- 1. fleet boots from the sealed ring checkpoint
        deadline = time.monotonic() + 300
        while True:
            assert proc.poll() is None, proc.communicate(timeout=30)[1][-4000:]
            try:
                status, health = _get_json(port, "/healthz", timeout=5)
                if status == 200 and health["workers_healthy"] == 2:
                    break
            except OSError:
                pass
            assert time.monotonic() < deadline, "serve --fleet never came up"
            time.sleep(1.0)

        status, events = _post_generate(port, "t5 t6 t7")
        assert status == 200
        assert sum(1 for e in events if e.get("done")) == 1

        # ---- 2. a good checkpoint lands: canary -> probation -> promoted
        _save_ring_step(ring, 20, params1)
        deadline = time.monotonic() + 120
        while True:
            status, table = _get_json(port, "/fleet")
            gens = [w["weights_generation"] for w in table["workers"]]
            if gens == [1, 1]:
                break
            assert time.monotonic() < deadline, f"promotion never landed: {table}"
            time.sleep(0.5)
        status, events = _post_generate(port, "t9 t10")
        done = [e for e in events if e.get("done")]
        assert len(done) == 1 and done[0]["finish_reason"] in ("eod", "budget")

        # ---- 3. a poisoned checkpoint lands: the canary errors under traffic
        # and probation rolls it back — generation 2 never reaches the fleet
        _save_ring_step(ring, 30, poisoned)
        from modalities_tpu.telemetry.metrics import parse_prometheus_text

        saw_rollback = False
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            _post_generate(port, "t5 t6")  # keep traffic flowing at the canary
            _, metrics_text = _raw_metrics(port)
            parsed = parse_prometheus_text(metrics_text)
            if parsed.get("fleet_rollbacks_total", {}).get((), 0.0) >= 1.0:
                saw_rollback = True
                break
            time.sleep(0.2)
        assert saw_rollback, "poisoned generation was never rolled back"
        # /fleet reflects the router's last health scrape: give it a probe
        # interval or two to observe the post-rollback generations
        deadline = time.monotonic() + 30
        while True:
            _, table = _get_json(port, "/fleet")
            if all(w["weights_generation"] == 1 for w in table["workers"]):
                break
            assert time.monotonic() < deadline, f"rollback never visible: {table}"
            time.sleep(0.2)

        # the donor generation keeps serving after the rollback
        status, events = _post_generate(port, "t5 t6 t7")
        done = [e for e in events if e.get("done")]
        assert status == 200 and len(done) == 1
        assert done[0]["finish_reason"] in ("eod", "budget")

        # ---- 4. SIGTERM drains the whole tier to exit 0
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def _raw_metrics(port, timeout=10.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()
