"""Tests of the benchmark itself, on the CPU: name resolution, the
FLOP/byte counts, the trace reduction, the order statistics, the refusal
without an accelerator, extension by files alone, and the correctness
check at a tiny size, with its float8 control and planted faults."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import flops, spec, stats, trace, workload
from bench.trace import Event

ROOT = Path(__file__).resolve().parent.parent
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _spec():
    return spec.load_spec(ROOT)


# ------------------------------------------------------------- names --
def test_every_name_resolves_to_its_files():
    s = _spec()
    for p in s["paths"]:
        assert (ROOT / p).is_dir()
    for c in s["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"]
        assert (ROOT / "bench" / "refs" / f"{cfg['arch']}.py").exists()
        assert (ROOT / "bench" / "adapters" / f"{cfg['arch']}.py").exists()
    for w in s["workloads"]:
        cell = spec.cell(ROOT, w["name"])
        assert (ROOT / "bench" / f"drive_{cell.mix['mode']}.py").exists()
        assert cell.limits, f"{w['name']} has no limits file"
        assert {m["name"] for m in cell.per_layer} == {
            m["name"] for m in s["per_layer"]
            if w["name"] in m.get("workloads", [w["name"]])}
        for m in cell.per_layer:
            assert callable(cell.readers[m["name"]])
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert "TPU v5 lite" in json.loads(
        (ROOT / "bench" / "peaks.json").read_text())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peak(ROOT, "no such chip")


# ------------------------------------------------------- FLOPs/bytes --
def _cfg(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def test_param_counts_match_hand_counts():
    # qwen2_5_3b: per layer q 2048x2048, k/v 2048x256 each, o 2048x2048,
    # biases 2048+256+256, MLP 3x2048x11008, two norm scales of 2048;
    # tied embedding 151936x2048; final norm 2048
    layer = (2048 * 2048 * 2 + 2048 * 256 * 2 + 2560
             + 3 * 2048 * 11008 + 2 * 2048)
    assert flops.param_count(_cfg("qwen2_5_3b")) == \
        36 * layer + 151936 * 2048 + 2048 == 3_085_938_688
    # olmo_1b: MHA 4x2048x2048, MLP 3x2048x8192, no norm parameters
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert flops.param_count(_cfg("olmo_1b")) == \
        16 * layer + 50304 * 2048 == 1_176_764_416


@pytest.mark.parametrize("name,kv_token,matmul", [
    # K and V: 2 x layers x kv heads x 128 x 2 bytes
    ("qwen2_5_3b", 2 * 36 * 2 * 128 * 2,
     2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008),
    ("olmo_1b", 2 * 16 * 16 * 128 * 2, 4 * 2048 * 2048 + 3 * 2048 * 8192),
])
def test_decode_and_prefill_costs_match_hand_counts(name, kv_token, matmul):
    cfg = _cfg(name)
    m = flops.dims(cfg)
    assert flops.kv_bytes_per_token(cfg) == kv_token
    assert flops.layer_matmul_params(cfg) == matmul
    # a batch of 4 live sequences at contexts 100..400 (new token included)
    f, b = flops.decode_cost(cfg, [100, 200, 300, 400])
    assert f == (2 * m["L"] * matmul * 4
                 + 4 * m["L"] * m["H"] * 128 * 1000
                 + 2 * m["V"] * 2048 * 4)
    assert b == flops.param_count(cfg) * 2 + kv_token * (1000 + 4)
    # a final 512-token chunk after 1024 cached positions
    f, b = flops.prefill_cost(cfg, 1024, 512, True)
    keys = 512 * 1024 + 512 * 513 // 2
    assert f == (2 * m["L"] * matmul * 512 + 4 * m["L"] * m["H"] * 128 * keys
                 + 2 * m["V"] * 2048)
    assert b == flops.param_count(cfg) * 2 + kv_token * 1536


def test_roofline_takes_the_larger_bound():
    assert flops.roofline_seconds(197e12, 0, PEAK) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, PEAK) == pytest.approx(1.0)
    assert flops.roofline_seconds(197e12, 2 * 819e9, PEAK) == \
        pytest.approx(2.0)


# ------------------------------------------------------------- trace --
def _synthetic():
    dev, host = "/device:TPU:0", "/host:CPU"
    mod, ops = trace.MODULE_LINE, trace.OPS_LINE
    f1 = "%fusion.1 = bf16[8,16]{1,0:T(8,128)} fusion(bf16[8,16] %p), kind=kLoop"
    loop = "%while.2 = (s32[], bf16[1,512,64]) while((s32[]) %t), body=%b"
    return [
        Event(dev, mod, "jit_decode_step(7)", 0, 100),
        Event(dev, ops, loop, 0, 100),               # holds the next two
        Event(dev, ops, f1, 0, 60),
        Event(dev, ops, "%copy.2 = bf16[4]{0} copy(bf16[4]{0} %x)", 50, 50),
        Event(dev, mod, "jit__unknown(9)", 300, 200),  # a prompt chunk
        Event(dev, ops, loop, 300, 200),
        Event(dev, ops, f1, 300, 200),
        Event(dev, mod, "jit__unknown(3)", 520, 20),   # a slot reset
        Event(dev, ops, "%copy.3 = bf16[4]{0} copy(bf16[4]{0} %y)", 520, 20),
        Event(dev, mod, "jit_decode_step(7)", 600, 100),
        Event(dev, ops, f1, 600, 100),
        Event(dev, mod, "jit__lambda(5)", 800, 10),      # gap 700..800:
        Event(dev, ops, "%fusion.9 = s32[8]{0} fusion()", 800, 10),  # no span
        Event(host, "python", "bench.engine_step", 90, 600),
        Event(host, "python", "bench.schedule", 110, 150),  # gap 100..300
        Event(host, "python", "bench.submit", 530, 40),     # gaps 500..520,
        Event(host, "python", "unrelated", 0, 1000),        # 540..600
    ]


def _label(module, loop):
    from bench.drive_real import program_label
    return program_label(module, loop)


def test_trace_busy_union_and_program_time():
    ev = _synthetic()
    assert trace.busy_seconds(ev) == pytest.approx(430e-9)
    prog = lambda p: (lambda m, loop: _label(m, loop) == p)
    assert trace.program_time(ev, prog("decode_step")) == \
        (pytest.approx(200e-9), 2)
    assert trace.program_time(ev, prog("prefill")) == (pytest.approx(200e-9), 1)
    # two chips: times average over the device planes
    two = ev + [Event("/device:TPU:1", e.line, e.name, e.start_ns,
                      3 * e.dur_ns) for e in ev if e.plane.startswith("/dev")]
    # second plane: 0..300, 300..900 (520..580 inside), 800..830 inside
    assert trace.busy_seconds(two) == pytest.approx((430 + 900) / 2 * 1e-9)


def test_trace_top_ops_are_named_by_program_and_skip_loops():
    top = dict((k, v) for k, v in trace.top_ops(_synthetic(), label=_label))
    assert top == {"decode_step/fusion.1 bf16[8,16]": pytest.approx(160e-9),
                   "prefill/fusion.1 bf16[8,16]": pytest.approx(200e-9),
                   "decode_step/copy.2 bf16[4]": pytest.approx(50e-9),
                   "_unknown/copy.3 bf16[4]": pytest.approx(20e-9),
                   "_lambda/fusion.9 s32[8]": pytest.approx(10e-9)}


def test_trace_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict((k, v) for k, v in trace.idle_gaps(_synthetic()))
    assert gaps == {"bench.schedule": pytest.approx(200e-9),
                    "bench.engine_step": pytest.approx(20e-9),
                    "bench.submit": pytest.approx(60e-9),
                    "no span": pytest.approx(100e-9)}


def test_short_names():
    assert trace.short_program("jit_decode_step(12)") == "decode_step"
    assert trace.short_program("jit__prefill_slot") == "_prefill_slot"
    assert trace.short_op("%copy.107 = bf16[36,32]{4,3:T(2,128)} copy(x)") \
        == "copy.107 bf16[36,32]"


# ------------------------------------------------------------- stats --
def test_percentile_is_nearest_rank_and_exact():
    xs = list(range(1, 101))                       # 1..100
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0, 1.0, 2.0], 95) == 3.0
    assert stats.percentile([0.2] * 19 + [float("inf")], 95) == 0.2
    assert stats.percentile([0.2] * 18 + [float("inf")] * 2, 95) == \
        float("inf")
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_rates_and_windows():
    assert stats.rate(510, 51.0) == 10.0
    assert stats.count_in([0.0, 0.5, 1.0, 1.5], 0.5, 1.5) == 2


# ---------------------------------------------------------- workload --
@pytest.mark.parametrize("traffic", ["chat", "longdoc"])
def test_every_seed_gets_the_same_work_in_another_order(traffic):
    mix = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                     .read_text())
    a = workload.generate(mix, 2**33 + 5, 51, 50304)
    b = workload.generate(mix, 17, 51, 50304)
    win = lambda items: [i for i in items if i.in_window]
    assert len(win(a)) == len(win(b)) == workload.window_count(mix, 51)
    assert sorted(len(i.prompt) for i in win(a)) == \
        sorted(len(i.prompt) for i in win(b))
    assert sorted(i.max_new_tokens for i in win(a)) == \
        sorted(i.max_new_tokens for i in win(b))
    assert [len(i.prompt) for i in win(a)] != [len(i.prompt) for i in win(b)]
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= len(i.prompt) <= hi for i in a)
    if mix["arrival"]["kind"] == "exp_gaps":
        gaps = lambda items: sorted(np.round(np.diff(
            [0.0] + [i.due for i in win(items)]), 9))
        assert gaps(a) == gaps(b)
        assert max(i.due for i in win(a)) < 51
        assert min(i.due for i in a if not i.in_window) >= 51 * 0.99
    assert workload.generate(mix, 17, 51, 50304)[3].prompt == b[3].prompt


def test_stratified_order_gives_every_block_one_value_per_stratum():
    values = np.arange(64)
    out = workload.shuffle(values, workload.seed_rng(2**33 + 3, 0), 8)
    assert sorted(out) == list(values)
    for j in range(8):
        block = out[8 * j:8 * (j + 1)]
        assert sorted(v // 8 for v in block) == list(range(8))
    other = workload.shuffle(values, workload.seed_rng(5, 0), 8)
    assert list(other) != list(out)


def test_lognormal_quantiles_keep_the_stated_mean():
    q = workload.length_quantiles(
        {"dist": "lognormal", "mean": 700, "sigma": 0.6, "min": 1,
         "max": 10**9}, 20000)
    assert np.mean(q) == pytest.approx(700, rel=0.01)


# ------------------------------------------------ no accelerator found --
def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen2_5_3b.chat", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


# ------------------------------------------------ extension by files --
def test_a_config_traffic_and_metric_are_added_by_files_alone(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    s = _spec()
    cfg = dict(_cfg("olmo_1b"), num_hidden_layers=2)
    (tmp_path / "bench" / "configs" / "olmo_1b_2l.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench" / "traffic" / "chat.json").read_text())
    mix["arrival"]["rate"] = 1.5
    (tmp_path / "bench" / "traffic" / "slow_chat.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "decode_calls.py").write_text(
        "def read(obs):\n    return float(len(obs.calls)) or None\n")
    s["configs"].append({"name": "olmo_1b_2l", "source": "x",
                         "file": "bench/configs/olmo_1b_2l.json",
                         "reduced": ["num_hidden_layers"], "why": "x"})
    s["workloads"].append({"name": "olmo_1b_2l.slow_chat",
                           "config": "olmo_1b_2l", "traffic": "slow_chat",
                           "chips": 1, "why": "x"})
    s["end_to_end"][0].setdefault("workloads", []).append(
        "olmo_1b_2l.slow_chat")
    s["per_layer"].append({"name": "decode_calls.ttft", "unit": "1",
                           "better": "lower", "source": "program_counter",
                           "layer": "runner", "moves": s["end_to_end"][0]["name"],
                           "workloads": ["olmo_1b_2l.slow_chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    cell = spec.cell(tmp_path, "olmo_1b_2l.slow_chat")
    assert cell.config["num_hidden_layers"] == 2
    assert cell.mix["arrival"]["rate"] == 1.5
    assert [m["name"] for m in cell.per_layer] == ["decode_calls.ttft"]

    class Obs:
        calls = [{}, {}, {}]
    assert cell.readers["decode_calls.ttft"](Obs()) == 3.0
    # and a name shared by suffix variants reads through its base file
    assert spec.reader_path(ROOT, "decode_step_ms.tpot").name == \
        "decode_step_ms.py"


# --------------------------------------------- correctness, tiny size --
# at this size sound runs read gaps of 0 to 0.02 and the float8 control
# 0.13 and more
TINY_LIMIT = {"logit_gap": {"limit": 0.05}}


def _tiny(name):
    cell = spec.cell(ROOT, name)
    c = cell.config
    cell.config = dict(
        c, num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2 if c["num_key_value_heads"] < 16 else 4,
        head_dim=16, intermediate_size=128, vocab_size=512,
        serving=dict(c["serving"], slots=4, max_len=256,
                     max_batched_tokens=64))
    mix = json.loads(json.dumps(cell.mix))
    mix["prompt"].update(min=20, max=150, mean=60)
    if mix["output"]["dist"] == "lognormal":
        mix["output"].update(min=4, max=60, mean=20)
    if mix["arrival"]["kind"] == "exp_gaps":
        mix["arrival"]["rate"] = 8.0
        mix["tail_seconds"] = 2
    else:
        mix["arrival"]["requests"] = 24
    mix["check"] = {"served_tokens": 200, "max_requests": 8}
    cell.mix = mix
    cell.limits = TINY_LIMIT
    return cell


@pytest.mark.parametrize("gap,unanswered,correct", [
    (0.0, 0, True), (0.05, 0, True), (0.0501, 0, False), (0.01, 1, False),
    (None, 0, False), (float("inf"), 0, False)])
def test_judge_holds_each_number_to_its_limit(gap, unanswered, correct):
    from bench import drive_real
    checks, ok = drive_real.judge(gap, unanswered, TINY_LIMIT)
    assert ok is correct
    assert checks["logit_gap"] == {"value": gap, "limit": 0.05}
    assert checks["unanswered"] == {"value": unanswered, "limit": 0}


def _run(cell, seed, **kw):
    from bench import drive_real
    return drive_real.run(cell, seed, 2.0, False, time.monotonic(), PEAK,
                          **kw)


def _token_altered(stack):
    execute = stack.runner.execute
    vocab = stack.runner.model.cfg.vocab_size

    def bad(out):
        return {rid: (t + 1) % vocab for rid, t in execute(out).items()}
    stack.runner.execute = bad


def _state_unchanged(stack):
    """Decode and prompt chunks return their logits but leave the cache as
    it was."""
    import functools

    import jax

    from repro.serving.model_runner import _prefill_slot
    runner = stack.runner
    decode = jax.jit(runner.model.decode_step)
    prefill = jax.jit(functools.partial(_prefill_slot, runner.model))

    def stale_decode(params, cache, tokens):
        return decode(params, cache, tokens)[0], cache

    def stale_prefill(params, cache, *args):
        return prefill(params, cache, *args)[0], cache
    runner._decode = stale_decode
    runner._prefill = stale_prefill


def _half_batch(stack):
    decode = stack.runner.decode

    def half(feeds):
        return decode({s: f for i, (s, f) in enumerate(sorted(feeds.items()))
                       if i % 2 == 0})
    stack.runner.decode = half


def _half_chunk(stack):
    """Each prompt chunk computes only its second half: the first half's
    keys and values are never written (the batch of a step that runs one
    prompt chunk and no decode)."""
    prefill_chunk = stack.runner.prefill_chunk

    def half(slot, chunk, start):
        cut = len(chunk) // 2
        return prefill_chunk(slot, chunk[cut:], start + cut)
    stack.runner.prefill_chunk = half


CELLS = ["qwen2_5_3b.chat", "olmo_1b.longdoc"]


@pytest.mark.parametrize("name", CELLS)
def test_tiny_cell_is_correct_and_its_control_is_not(name):
    res = _run(_tiny(name), 2**33 + 11, control=True)
    assert res["correct"], res["checks"]
    assert res["info"]["requests_checked"] >= 8
    assert res["info"]["served_tokens_checked"] >= res["info"]["requests_checked"]
    ctrl = res["control"]
    assert not ctrl["correct"], ctrl["checks"]
    assert ctrl["checks"]["logit_gap"]["limit"] == \
        TINY_LIMIT["logit_gap"]["limit"]
    assert ctrl["checks"]["unanswered"] == res["checks"]["unanswered"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch, _half_chunk])
def test_a_broken_timed_path_is_not_correct(fault, name):
    res = _run(_tiny(name), 2**33 + 13, hooks=(fault,))
    assert not res["correct"], res["checks"]
