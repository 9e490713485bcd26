"""A rehearsal of the whole run on the CPU at toy widths (tests/tiny.json,
and tests/tiny_moe.json for an architecture of its own module): engine,
weights, open loop, metrics and the check, in this process. It proves the
path and the check's power; no number here is a device number, and nothing
is printed as a result line."""
import argparse
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import harness
import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


# The readers of a latency cell, which no cell of BENCHMARK.json uses yet:
# the rehearsal reads them too, so that every reader in metrics/ runs.
LATENCY = {"end_to_end": [{"name": "ttft_p90_ms", "unit": "ms"},
                          {"name": "tpot_p90_ms", "unit": "ms"}],
           "per_layer": [{"name": "sched.queue_wait_p90_ms.ttft",
                          "unit": "ms"},
                         {"name": "kv.prefix_hit_rate.ttft", "unit": "%"}]}


def rehearse(seed, trace=0, seconds=3.0, mix="tiny_chat", config="tiny"):
    cfg = harness.load_json(os.path.join(HERE, config + ".json"))
    mix = harness.load_json(os.path.join(HERE, mix + ".json"))
    bm = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl = {"name": "tiny.chat", "traffic": "tiny_chat", "chips": 1,
          "metrics": {k: bm[k] + LATENCY[k]
                      for k in ("end_to_end", "per_layer")}}
    args = argparse.Namespace(workload=wl["name"], seed=seed,
                              seconds=seconds, trace=trace)
    return run.run_cell(wl, cfg, mix, args, jax.devices(),
                        time.perf_counter())


@pytest.mark.parametrize("config", ["tiny", "tiny_moe"])
def test_rehearsal_is_correct_and_reports_every_metric(capsys, config):
    res = rehearse(2**33 + 21, config=config)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                   "output_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    assert res["device"]["platform"] == "cpu"
    assert '"metrics"' not in capsys.readouterr().out


def test_traced_rehearsal_reads_the_program_layers():
    res = rehearse(5, trace=1)
    assert res["correct"] is True
    got = set(res["metrics"])
    assert {"tick.host_ms.tput", "sched.queue_wait_p90_ms.ttft",
            "kv.prefix_hit_rate.ttft", "kv.occupancy.tput",
            "sched.batch_occupancy.tput"} <= got
    # no chip, no device trace: those readers find nothing and stay silent
    assert not any(k.startswith(("step.mfu", "device.idle",
                                 "paged_decode_roofline")) for k in got)
    assert "busy_s" not in res["device"]


def test_backlog_rehearsal_fills_the_batch_before_the_window():
    res = rehearse(2**32 + 9, mix="tiny_decode")
    assert res["correct"] is True
    assert res["metrics"]["output_tokens_per_s"]["value"] > 0
    # filling the batch is set-up: every slot had a token before the window
    assert res["metrics"]["setup_s"]["value"] > 0


def _alter_token(monkeypatch):
    from repro.serving.engine import VariantBackend
    orig = VariantBackend._finish

    def finish(self, r, tokens, now):
        tokens = list(tokens)
        i = len(tokens) // 2
        tokens[i] = (tokens[i] + 1) % self.cfg.vocab_size
        return orig(self, r, tokens, now)
    monkeypatch.setattr(VariantBackend, "_finish", finish)


def _stale_cache(monkeypatch):
    """Decode steps and prefill chunks that return the KV pool they were
    given: which of the two serves a token depends on the schedule."""
    from repro.models.model import LM
    dec, chunk = LM.decode_step_paged, LM.prefill_chunk_paged

    def step(self, params, cache, tokens, *, n_pages):
        logits, new = dec(self, params, cache, tokens, n_pages=n_pages)
        return logits, dict(new, kp=cache["kp"], vp=cache["vp"])

    def fused(self, params, cache, *args):
        logits, new = chunk(self, params, cache, *args)
        return logits, dict(new, kp=cache["kp"], vp=cache["vp"])
    monkeypatch.setattr(LM, "decode_step_paged", step)
    monkeypatch.setattr(LM, "prefill_chunk_paged", fused)


# The faults a served cell can have. Half a batch left out of a mean is a
# training fault, and one chip has no exchange between chips to leave out.
@pytest.mark.parametrize("fault", [_alter_token, _stale_cache],
                         ids=["token_altered", "state_unchanged"])
def test_check_fails_a_broken_timed_path(monkeypatch, fault):
    from repro.models.model import _cached_lm
    _cached_lm.cache_clear()
    fault(monkeypatch)
    try:
        res = rehearse(17)
    finally:
        _cached_lm.cache_clear()
    assert res["correct"] is False, res["checks"]


def test_moe_check_fails_with_one_expert_permuted(monkeypatch):
    """The MoE reference given expert 0's input weights with their rows
    reversed: the served tokens no longer match it."""
    form = harness.arch_module(harness.load_json(
        os.path.join(HERE, "tiny_moe.json")))
    right = form.layer_weights

    def permuted(key, i, a, rung, dtype):
        w = right(key, i, a, rung, dtype)
        return dict(w, wi=w["wi"].at[0].set(w["wi"][0][::-1]))
    monkeypatch.setattr(form, "layer_weights", permuted)
    res = rehearse(2**33 + 21, config="tiny_moe")
    assert res["correct"] is False, res["checks"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ds67b-pp16.decode",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_machine_without_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_fails_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_control_fails_the_limit():
    """The reference in float8 put in the program's place: its reading is
    over the limit that the served tokens keep (toy widths, CPU)."""
    import control
    cfg = harness.load_json(os.path.join(HERE, "tiny.json"))
    mix = harness.load_json(os.path.join(HERE, "tiny_chat.json"))
    got = dict(control.readings({"name": "tiny.chat"}, cfg, mix, 23, 3.0))
    limit = cfg["check"]["max_logit_gap"]
    assert set(got) == {"L1", "L2"}
    for g in got.values():
        assert g["gap"] <= limit < g["control_gap"]


def test_moe_control_fails_the_cell():
    """The sparse-expert module's float8 control: over the limit on a rung
    (about one token in five changes its argmax at these widths, so a rung
    of a dozen served tokens may see none change), while the served tokens
    keep the limit on every rung."""
    import control
    cfg = harness.load_json(os.path.join(HERE, "tiny_moe.json"))
    mix = harness.load_json(os.path.join(HERE, "tiny_chat.json"))
    got = dict(control.readings({"name": "tiny_moe.chat"}, cfg, mix, 23, 3.0))
    limit = cfg["check"]["max_logit_gap"]
    assert set(got) == {"L1", "L2"}
    assert all(g["gap"] <= limit for g in got.values())
    assert any(g["control_gap"] > limit for g in got.values())
