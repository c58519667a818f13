"""The harness driven on the CPU at a tiny size, past its look for a
chip: on a temporary copy to which a configuration, two traffic mixes
and a per-layer metric were added as new files plus appended entries.
Sound runs come out correct; the lower-precision control and a timed
path broken underneath come out not correct."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_copy
from benchmark import weights
from benchmark.reference import gpt2 as ref

SEED = 2**31 + 77


@pytest.fixture()
def harness(tmp_path, monkeypatch):
    return tiny_copy.point_harness_at(monkeypatch, tiny_copy.make(tmp_path))


def run(harness, cell, **kw):
    return harness.run_cell("tiny.tiny-" + cell, SEED, 2.0,
                            kw.pop("trace", 0), time.time(),
                            need_tpu=False, keep_checks=True, **kw)


def test_reference_agrees_with_the_programs_model():
    from deepspeed_tpu.models.gpt2 import GPT2ForCausalLM, gpt2_config
    sizes = tiny_copy.TINY_SIZES
    cfg = gpt2_config("gpt2-tiny", dropout=0.0, dtype=jnp.float32)
    model = GPT2ForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, 512, (4, 128)).astype(np.int32)
    template = jax.eval_shape(
        lambda k: model.init(k, {"input_ids": ids[:1]}),
        jax.random.PRNGKey(0))
    flat = weights.make_weights(sizes, SEED, jnp.float32)
    again = weights.make_weights(sizes, SEED, jnp.float32)
    assert all(np.array_equal(flat[k], again[k]) for k in flat)
    tree = weights.to_program_tree(flat, template)
    got = ref.logits(flat, jnp.asarray(ids), sizes["n_head"])
    want = model.apply(tree, ids)
    # float32 against float32: rounding in another order only
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())
    loss, grads = jax.value_and_grad(lambda p: model.loss_fn(
        p, {"input_ids": ids}, deterministic=True))(tree)
    ref_loss, ref_grads = ref.TrainFollower(
        flat, sizes["n_head"], rows_per_block=2).loss_and_grads(ids)
    assert abs(float(loss) - ref_loss) < 1e-5
    grads = weights.from_program_tree(grads)
    for k, g in ref_grads.items():
        assert float(jnp.linalg.norm(grads[k] - g)) < \
            1e-5 * float(jnp.linalg.norm(g)), k


def test_training_cell_sound_run_with_added_files(harness):
    result = run(harness, "train", trace=1)
    assert result["correct"] and result["failed"] == 0, result["checks"]
    # the traced line carries the cell's per-layer metrics, the dummy
    # one that was added as a new file among them
    assert result["metrics"]["dummy_metric"] == {"value": 42.0,
                                                 "unit": "count"}
    assert result["metrics"]["compiles_in_window.train"]["value"] == 0
    assert "train_tokens_per_s_per_chip" not in result["metrics"]
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes", "window_s"}
    assert "device_ops" in result["breakdown"]
    plain = run(harness, "train")
    assert set(plain["metrics"]) == {"train_tokens_per_s_per_chip",
                                     "setup_s"}
    assert plain["metrics"]["train_tokens_per_s_per_chip"]["value"] > 0


def test_training_control_fp8_reference_is_not_correct(harness):
    """The cell's control: the reference computed in fp8 (projection
    operands rounded to float8_e4m3fn) in the program's place. Tiny
    limits: sound runs read a loss gap of 1e-5 to 4e-5 here."""
    result = run(harness, "train", control=1, check_only=True)
    assert not result["correct"]
    over = [c["name"] for c in result["checks"] if not c["ok"]]
    assert any(name.startswith("loss_abs") for name in over), result["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_batch"])
def test_training_broken_underneath_is_not_correct(harness, monkeypatch,
                                                   fault):
    import deepspeed_tpu
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    if fault == "state_unchanged":
        # a step that leaves the parameters where they were
        real = deepspeed_tpu.initialize

        def frozen(*a, config=None, **k):
            config = harness.merged(config, {"scheduler": {"params": {
                "warmup_max_lr": 0.0}}})
            return real(*a, config=config, **k)
        monkeypatch.setattr(deepspeed_tpu, "initialize", frozen)
        expect = "dp_along_mu_rel"
    else:
        # a step that leaves out half of the batch
        real = DeepSpeedEngine.train_batch

        def halved(self, data_iter=None, batch=None):
            ids = np.array(batch["input_ids"])
            half = ids.shape[1] // 2
            ids[:, half:] = ids[:, :half]
            return real(self, data_iter, {"input_ids": ids})
        monkeypatch.setattr(DeepSpeedEngine, "train_batch", halved)
        expect = "loss_abs"
    result = run(harness, "train", check_only=True)
    assert not result["correct"]
    assert any(c["name"].startswith(expect) and not c["ok"]
               for c in result["checks"]), result["checks"]


def test_serving_cell_sound_run(harness):
    result = run(harness, "serve")
    assert result["correct"] and result["failed"] == 0, result["checks"]
    assert result["attempted"] == 8          # 4/s over 2 s, every seed
    assert set(result["metrics"]) == {"itl_mean_ms", "serve_tokens_per_s",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def test_serving_traced_run_reads_the_client_in_the_window(harness):
    """A traced run is an untraced one with a traced tail after the
    drain: the client's and the scheduler's numbers come from the same
    window of the same requests, which the profiler never touches."""
    from benchmark.kinds import serve_open
    seen = {}
    real = serve_open.summarise

    def keep(s, seconds):
        seen["due"] = sorted(r.rid for r in s["due"])
        seen["tail"] = [r.rid for r in s["all"] if r.rid.startswith("t")]
        seen["calls"] = {span for _, span, _ in s["recorder"].calls}
        return real(s, seconds)
    serve_open.summarise, undo = keep, real
    try:
        result = run(harness, "serve", trace=1)
        traced = dict(seen)
        run(harness, "serve")
    finally:
        serve_open.summarise = undo
    assert result["correct"] and result["attempted"] == 8
    assert traced["due"] == seen["due"] and traced["tail"] and \
        not seen["tail"]
    assert traced["calls"] >= {"bench/prefill", "bench/step", "bench/fence"}
    assert set(result["metrics"]) >= {
        "ttft_observed_mean_ms", "ttft_p90_ms", "itl_p95_ms",
        "queue_wait_mean_ms", "slots_occupied_mean",
        "compiles_in_window.serve"}
    assert result["device"]["window_s"] > 0


def test_control_2_switches_the_programs_own_int8_weights_on(harness,
                                                             monkeypatch):
    """`--control 2` lays the mix's `control_program` over its
    `inference` block; a mix that names none is refused."""
    from deepspeed_tpu.inference import engine as engine_mod
    calls = []
    real = engine_mod.quantize_param_tree
    monkeypatch.setattr(
        engine_mod, "quantize_param_tree",
        lambda *a, **k: calls.append(1) or real(*a, **k))
    result = run(harness, "serve", control=2)
    assert calls and result["failed"] == 0 and result["checks"]
    with pytest.raises(SystemExit):
        run(harness, "train", control=2, check_only=True)


def test_serving_broken_underneath_is_not_correct(harness, monkeypatch):
    """A token altered where it is produced: the fence hands the loop
    other tokens than the engine decoded."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    real = InferenceEngine.fetch_state

    def altered(self):
        snap = real(self)
        snap["out_tokens"] = (snap["out_tokens"] + 1) % 512
        return snap
    monkeypatch.setattr(InferenceEngine, "fetch_state", altered)
    result = run(harness, "serve")
    assert not result["correct"]
    assert any(c["name"] == "served_gap_max" and not c["ok"]
               for c in result["checks"])


def test_token_gaps_count_every_delivered_token():
    from benchmark.kinds.serve_open import token_gaps, weighted_percentile
    deliveries = {"a": [(0.5, 0), (1.0, 2), (2.0, 6), (3.5, 8)],
                  "b": [(1.9, 4), (2.4, 8)]}
    total, gaps, delivered, per = token_gaps(deliveries, 1.0, 3.0)
    # a: 2 at 1.0 (first: 1 gap of 0), 4 at 2.0 after 1.0 s;
    # b: 4 at 1.9 (first: 3 gaps of 0), 4 at 2.4 after 0.5 s
    assert delivered == 14 and gaps == 1 + 4 + 3 + 4
    assert total == pytest.approx(1.5)
    assert sorted(per) == [(0.125, 4), (0.25, 4)]
    assert weighted_percentile(per, 95) == 0.25


@pytest.mark.parametrize("cast", [None, "float8_e4m3fn"])
def test_live_slot_logits_against_the_reference(harness, cast):
    """The decode program's logits for slots in mid-flight agree with
    the reference within bf16 compute's rounding (0.003 to 0.005 of
    the largest logit here); the reference computed in fp8 in the
    program's place, the control, does not. (The program's own
    int8-weight path reads 0.004 to 0.005: block-64 int8 weights carry
    as many bits as bf16 compute, so it is no control.)"""
    from benchmark.kinds import serve_open
    from deepspeed_tpu.inference import Request, ServingLoop
    cell = harness.load_cell(harness.load_benchmark(), "tiny.tiny-serve")
    engine, flat, _ = serve_open.build_engine(cell, SEED)
    loop = ServingLoop(engine)
    rng = np.random.default_rng(3)
    for i, n in enumerate((5, 20, 37)):
        loop.submit(Request(rid=i, tokens=rng.integers(0, 512, n),
                            max_new_tokens=40))
    loop._t0, loop._last_fence_t = time.monotonic(), 0.0
    for _ in range(5):
        loop.step()
    live = serve_open.next_logits_of_live_slots(engine, loop)
    assert len(live) == 3 and all(len(seq) > 5 for seq, _ in live)
    checks = serve_open.compare_with_reference(
        flat, cell["sizes"], cell["mix"]["check"], [], live, 128,
        control_cast=cast)
    by_name = {c["name"]: c for c in checks}
    assert by_name["live_logits_rel"]["ok"] == (cast is None), checks
    # the loop goes on undisturbed: every request still gets its tokens
    while loop.live or loop.prefilling or loop.queue:
        loop.step()
    assert sorted(len(r.out_tokens) for r in loop.results) == [40, 40, 40]
