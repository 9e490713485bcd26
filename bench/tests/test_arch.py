"""The architecture modules behind ``harness.arch_module``: the Llama form
gives the weights, gaps and work counts that the dense-only harness gave,
the trace facts time each kernel a module names, and the sparse-expert
module of tests/tiny_moe.json charges a token only the experts it uses.
CPU, toy widths; no number here is a device number."""
import os
import shutil

import jax
import numpy as np
import pytest

import harness
import reference
import roofline
import run
import trace_reduce
import traffic
import weights
from _lib import kernel_roofline
from trace_reduce import Event, Trace

HERE = os.path.dirname(os.path.abspath(__file__))
KERNEL = "paged_flash_decode_bkhd"


def config(name):
    return harness.load_json(os.path.join(HERE, name + ".json"))


class StepClock:
    """A host clock that moves 1 ms a reading: the open loop then takes the
    same steps on every machine, however fast it runs."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def traced_window(cfg_name, mix_name, seed, seconds, trace_s):
    cfg = config(cfg_name)
    mix = harness.load_json(os.path.join(HERE, mix_name + ".json"))
    cell = harness.Cell(cfg, seed, trace=True, clock=StepClock())
    vocab = cfg["model"]["vocab_size"]
    cell.warm_up(vocab)
    plan = traffic.schedule(mix, cell.split, vocab, seed, seconds)
    out = cell.run_window(plan, seconds, drain_s=0.0, trace_s=trace_s,
                          fill=bool(mix.get("fill_before_window")))
    shutil.rmtree(out.trace_dir, ignore_errors=True)
    return cell, out


def test_a_configuration_without_arch_is_the_llama_form():
    form = harness.arch_module(config("tiny"))
    assert form is harness.arch_module(config("tiny"))      # loaded once
    assert form.__file__.endswith(os.path.join("bench", "arch", "llama.py"))
    assert harness.arch_module(config("tiny_moe")) is not form
    assert form.arch_of(config("tiny"), 2) == weights.Arch(
        d_model=128, num_heads=4, num_kv_heads=2, head_dim=32, d_ff=256,
        vocab=512, padded_vocab=512, num_layers=2, rope_theta=5000000.0,
        norm_eps=1e-05)


def test_llama_tree_is_the_weights_tree():
    form = harness.arch_module(config("tiny"))
    a = form.arch_of(config("tiny"), 2)
    seed = 2**33 + 5
    got = jax.jit(form.make_tree, static_argnums=(1, 2, 3))(
        form.run_key(seed), a, 1, "float32")
    want = jax.jit(weights.make_tree, static_argnums=(1, 2, 3))(
        weights.run_key(seed), a, 1, "float32")
    tu = jax.tree_util
    assert tu.tree_structure(got) == tu.tree_structure(want)
    for x, y in zip(tu.tree_leaves(got), tu.tree_leaves(want)):
        assert x.dtype == y.dtype and np.array_equal(x, y)


def test_llama_gaps_are_the_reference_gaps():
    form = harness.arch_module(config("tiny"))
    a = form.arch_of(config("tiny"), 2)
    rng = np.random.default_rng(3)
    samples = [(rng.integers(0, 512, n).astype(np.int32),
                rng.integers(0, 512, m).astype(np.int32))
               for n, m in ((20, 12), (64, 5))]
    key = form.run_key(2**32 + 1)
    kw = dict(max_len=76, max_rows=12)
    assert form.served_gaps(a, key, 1, "float32", samples, **kw) == \
        reference.served_gaps(a, key, 1, "float32", samples, **kw)
    got = form.served_gaps(a, key, 1, "float32", samples, mode="fp8", **kw)
    assert got == reference.served_gaps(a, key, 1, "float32", samples,
                                         control=True, **kw)
    assert set(got) == {"gap", "tokens", "control_gap"}


# (model FLOPs, model tokens, kernel FLOPs, kernel bytes) that the harness
# counted on these windows before the counts moved into the architecture
# modules; seed 2**32 + 9, the step clock, tiny.json
BEFORE = {"tiny_decode": (42540032.0, 75.0, 2830336.0, 1517568.0),
          "tiny_chat": (33473536.0, 55.0, 1276928.0, 693760.0)}


@pytest.mark.parametrize("mix, seconds, trace_s", [
    ("tiny_decode", 0.06, 0.05), ("tiny_chat", 0.5, 0.4)])
def test_llama_work_is_the_dense_count(mix, seconds, trace_s):
    cell, out = traced_window("tiny", mix, 2**32 + 9, seconds, trace_s)
    kinds = {t.kind for sn in out.snaps for t in sn.ticks}
    assert {"fused", "decode"} <= kinds
    w = harness.work_in_trace(cell, out)
    k = w["kernels"][KERNEL]
    assert (w["model_flops"], w["model_tokens"], k["flops"], k["bytes"]) \
        == BEFORE[mix]


def test_trace_facts_time_every_kernel_the_module_names(monkeypatch):
    """A hand-made device trace, one kernel event of 0.2 ms in each traced
    step: the facts carry it under ``kernels`` and under the module's name
    for it, and both roofline readers agree."""
    cell, out = traced_window("tiny", "tiny_decode", 2**32 + 9, 0.06, 0.05)
    host = [Event("bench.step", s.t0 * 1e9, (s.t1 - s.t0) * 1e9)
            for s in out.snaps]
    dev = [Event(f"%{KERNEL}.7 = f32[4,2,2,32] custom-call()",
                 s.t0 * 1e9 + 1e5, 2e5) for s in out.snaps]
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "load",
                        lambda path: Trace({"/device:TPU:0": dev}, host))
    out.trace_dir = "unused"
    red = harness.reduce_trace(cell, out, "TPU v5 lite")
    w = harness.work_in_trace(cell, out)
    n = len(out.snaps) - 1          # the first traced step opens the window
    want = dict(s=pytest.approx(n * 2e-4), calls=n, **w["kernels"][KERNEL])
    assert red["kernels"] == {KERNEL: want}
    assert {k: red["paged_decode_" + k] for k in want} == want
    facts = {"trace": red}
    assert run.reader("paged_decode_roofline.tput")(facts) == \
        kernel_roofline(facts, KERNEL) > 0
    assert kernel_roofline(facts, "no_such_kernel") is None
    assert kernel_roofline({"trace": None}, KERNEL) is None


def test_moe_work_charges_the_experts_a_token_uses():
    cfg = config("tiny_moe")
    form = harness.arch_module(cfg)
    a = form.arch_of(cfg, 2)
    L, D, F, H, KV, hd, E, k, V = 2, 128, 64, 4, 2, 32, 4, 2, 512
    # attention, router, the k experts a token is sent to, norms; the head
    active = L * (2 * D * H * hd + 2 * D * KV * hd + D * E + k * 3 * D * F
                  + 2 * D) + D * V
    from repro.configs.base import get_config
    prog = get_config(cfg["registry"]).replace(num_layers=L, **cfg["model"])
    assert active == prog.active_param_count() - V * D   # less the lookup
    step = harness.StepRows("decode", 4, [0, 5], [(0, 3), (5, 6)], [])
    model, kern = form.step_work(a, step)
    assert model == sum(2 * active + 4 * L * H * hd * (p + 1)
                        for p in (0, 1, 2, 5))
    calls = [roofline.paged_decode_call([c + 1, 5 + c + 1], H, KV, hd)
             for c in range(4)]
    assert kern == {KERNEL: (L * sum(f for f, _ in calls),
                             L * sum(b for _, b in calls))}
    # step.mfu reads these FLOPs over the window at the chip's peak
    pk = roofline.peaks("TPU v5 lite")
    facts = {"trace": {"model_flops": model, "window_s": 2.0, "peaks": pk}}
    assert run.reader("step.mfu.tput")(facts) == pytest.approx(
        100 * model / (2.0 * pk["bf16_flops"]))


def test_moe_layout_and_cache_bytes():
    cfg = config("tiny_moe")
    form = harness.arch_module(cfg)
    a = form.arch_of(cfg, 2)
    tree = jax.eval_shape(lambda key: form.make_tree(key, a, 0, "float32"),
                          form.run_key(7))
    assert tree["layers"]["ffn"]["wi"].shape == (2, 4, 128, 64)
    assert tree["layers"]["ffn"]["router"].shape == (2, 128, 4)
    assert form.kv_bytes_per_token(a) == 2 * 4 * 2 * 2 * 32
