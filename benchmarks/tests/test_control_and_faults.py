"""The control and the faults, at a size a test run can hold.

Each goes through a whole run's own comparison and has to come out not
correct under the limits. The control is the plain reference put in the
program's place in the nearest precision below bfloat16 (scaled float8); the
harness judges it with the same function and limits as the program
(``stand_ins`` in the last line). The faults break the timed path underneath
and have to turn the run's own ``correct`` false. The limits of the tiny
preset are set here from the program's own reading, as the cells' limits are
set from the chip's (there the control reads 10 to 17 times the program,
PERF.md section 2).
"""
import pytest

from presets import drive


def tight_limits(monkeypatch, preset, factor=3.0):
    """Limits for the tiny preset: ``factor`` times a sound run's reading."""
    from presets import PRESETS
    sound = drive(preset)["compared"]
    limits = {k: max(factor * v["value"], 1e-3)
              for k, v in sound.items() if v["limit"] is not None}
    monkeypatch.setitem(PRESETS[preset]["config"], "limits", limits)


def test_training_control_and_half_batch_come_out_not_correct(monkeypatch):
    tight_limits(monkeypatch, "resnet50-fit-synth")
    out = drive("resnet50-fit-synth", stand_ins=("fp8", "half_batch", "bf16"))
    assert out["correct"] is True
    for kind in ("fp8", "half_batch"):
        verdict = out["stand_ins"][kind]
        assert verdict["correct"] is False, kind
        assert any(c["limit"] is not None and c["value"] > c["limit"]
                   for c in verdict["compared"].values())
    assert set(out["stand_ins"]["bf16"]["compared"]) == set(
        out["stand_ins"]["fp8"]["compared"])


def test_serving_control_comes_out_not_correct(monkeypatch):
    # which requests finish inside a CPU window varies from run to run, and
    # a tiny model has few near ties: twice the sound reading here (the
    # chip's readings, a factor of ten apart, are in PERF.md)
    tight_limits(monkeypatch, "bloom1b7-saturated", factor=2.0)
    out = drive("bloom1b7-saturated", stand_ins=("fp8",), seconds=2.0)
    assert out["correct"] is True
    assert out["stand_ins"]["fp8"]["correct"] is False


def test_fault_step_returns_state_unchanged(monkeypatch):
    tight_limits(monkeypatch, "resnet50-fit-synth")
    import mxtpu.optimizer as opt
    monkeypatch.setattr(
        opt, "functional_optimizer_step",
        lambda optimizer, index, w, g, state, t, lr: (w, state))
    out = drive("resnet50-fit-synth")
    assert out["correct"] is False
    assert out["compared"]["delta_norm_gap"]["value"] > 0.99


def test_fault_half_of_the_batch_left_out(monkeypatch):
    tight_limits(monkeypatch, "resnet50-fit-synth")
    import jax
    from mxtpu.ops.registry import get_op
    op = get_op("SoftmaxOutput")
    sound = op.fn

    def halved(data, label, **kw):
        """Gradient from the first half of the rows only, doubled: the mean
        over the rest."""
        rows = data.shape[0] // 2
        mask = (jax.numpy.arange(data.shape[0]) < rows).astype(data.dtype)

        @jax.custom_vjp
        def scale(x):
            return x

        scale.defvjp(lambda x: (x, None),
                     lambda _r, g: (g * 2.0 * mask[:, None],))
        return sound(scale(data), label, **kw)

    monkeypatch.setattr(op, "fn", halved)
    out = drive("resnet50-fit-synth")
    assert out["correct"] is False


@pytest.mark.parametrize("cell", ["bloom1b7-saturated", "open-loop"])
def test_fault_token_altered_where_it_is_produced(monkeypatch, cell):
    tight_limits(monkeypatch, cell)
    from mxtpu.serving import InferenceEngine
    sound = InferenceEngine.gen_step

    def altered(self, state, params, aux):
        nxt, new_state = sound(self, state, params, aux)
        return (nxt + 1) % 512, new_state

    monkeypatch.setattr(InferenceEngine, "gen_step", altered)
    out = drive(cell, seconds=2.0)
    assert out["correct"] is False
