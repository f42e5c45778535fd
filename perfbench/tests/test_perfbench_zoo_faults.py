"""The zoo-train cell's check at a CPU size: a sound run is correct; with
the timed path broken underneath (a round that returns its state
unchanged, half of the batch left out of the loss) it is not; and the
control (the reference in fp8 in the program's place) reads above the
limits."""
import jax.numpy as jnp

from perfbench import harness
from perfbench.reference import compare
from perfbench.tests import tiny

LIMITS = tiny.LIMITS["tiny-zoo"]


def failed(res):
    return [k for k, c in res["checks"].items()
            if c["value"] is None or c["limit"] is None
            or c["value"] > c["limit"]]


def test_sound_run_is_correct(tmp_path):
    res = tiny.run(tmp_path, "tiny-zoo")
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["attempted"] >= 1 and res["failed"] == 0


def test_round_returning_its_state_unchanged_is_caught(tmp_path,
                                                       monkeypatch):
    from repro.engine.zoo_train import ZooTrainRound
    real = ZooTrainRound.round_train

    def frozen(self, state, *args, **kw):
        _, stats = real(self, state, *args, **kw)
        return state, stats

    monkeypatch.setattr(ZooTrainRound, "round_train", frozen)
    res = tiny.run(tmp_path, "tiny-zoo")
    assert res["correct"] is False
    assert {"grad_gap_median", "change_gap_median"} <= set(failed(res))


def test_half_of_the_batch_left_out_is_caught(tmp_path, monkeypatch):
    import repro.models.layers as layers
    real = layers.chunked_cross_entropy

    def half(x, targets, *, mask=None, **kw):
        B, S = targets.shape
        keep = jnp.broadcast_to(jnp.arange(S) < S // 2, (B, S))
        keep = keep.astype(jnp.float32)
        return real(x, targets, mask=keep if mask is None else mask * keep,
                    **kw)

    monkeypatch.setattr(layers, "chunked_cross_entropy", half)
    res = tiny.run(tmp_path, "tiny-zoo")
    assert res["correct"] is False
    assert failed(res)


def test_control_in_fp8_reads_above_the_limits(tmp_path):
    bench = tiny.make(tmp_path)
    ctx = harness.Context(harness.load_json(bench), "tiny-zoo", 1, tmp_path)
    driver = harness.driver_class(ctx)(ctx)
    driver.setup()
    driver.free()
    want = driver.reference(ctx.config["reference_policy"])
    got = driver.reference(ctx.config["control_policy"])
    nums = compare.numbers(got, want)
    ok, _ = compare.judge(nums, {k: {"limit": v} for k, v in LIMITS.items()})
    assert not ok, nums
