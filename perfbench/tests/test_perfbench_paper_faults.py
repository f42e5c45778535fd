"""The paper cell's check at a CPU size: a sound run is correct; with the
timed path broken underneath (a round that returns its state unchanged,
half of every worker's samples left out of its gradient, the schedule's
power scale altered where it is worked out) it is not; and
the control (the reference with its matmuls in three bfloat16 passes, the
``high`` precision, in the program's place) reads above the limits."""

from perfbench import harness
from perfbench.reference import compare
from perfbench.tests import tiny


def failed(res):
    return [k for k, c in res["checks"].items()
            if c["value"] is None or c["limit"] is None
            or c["value"] > c["limit"]]


def test_sound_run_is_correct(tmp_path):
    res = tiny.run(tmp_path, "tiny-paper")
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["paper_rounds_per_s"]["value"] > 0


def test_round_returning_its_state_unchanged_is_caught(tmp_path,
                                                       monkeypatch):
    from repro.engine import EngineRun
    real = EngineRun.run_chunk

    def frozen(self, state, *args, **kw):
        _, stats = real(self, state, *args, **kw)
        return state, stats

    monkeypatch.setattr(EngineRun, "run_chunk", frozen)
    res = tiny.run(tmp_path, "tiny-paper")
    assert res["correct"] is False
    assert {"update_gap", "change_gap"} <= set(failed(res))


def test_half_of_the_batch_left_out_is_caught(tmp_path, monkeypatch):
    import repro.models.mlp_mnist as mlp
    real = mlp.mlp_mnist_loss

    def half(params, x, y):
        n = x.shape[0] // 2
        return real(params, x[:n], y[:n])

    monkeypatch.setattr(mlp, "mlp_mnist_loss", half)
    res = tiny.run(tmp_path, "tiny-paper")
    assert res["correct"] is False
    assert "update_gap" in failed(res)


def test_schedule_answer_altered_is_caught(tmp_path, monkeypatch):
    import repro.engine.core as core
    real = core.admm_solve_batched_jit

    def halved(*args, **kw):
        beta, b_t, *rest = real(*args, **kw)
        return (beta, 0.5 * b_t, *rest)

    monkeypatch.setattr(core, "admm_solve_batched_jit", halved)
    res = tiny.run(tmp_path, "tiny-paper")
    assert res["correct"] is False
    assert "bt_gap" in failed(res)


def test_control_in_three_passes_reads_above_the_limits(tmp_path):
    bench = tiny.make(tmp_path)
    ctx = harness.Context(harness.load_json(bench), "tiny-paper", 1,
                          tmp_path)
    driver = harness.driver_class(ctx)(ctx)
    driver.setup()
    driver.free()
    want = driver.reference(ctx.config["reference_policy"])
    got = driver.reference(ctx.config["control_policy"])
    nums = compare.numbers(got, want)
    ok, _ = compare.judge(nums, {k: {"limit": v} for k, v
                                 in tiny.LIMITS["tiny-paper"].items()})
    assert not ok, nums
