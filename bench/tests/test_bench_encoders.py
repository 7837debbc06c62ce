"""A configuration brings its encoder as one module under bench/encoders:
the harness runs a non-MLP encoder from its module alone, hands the
encoder's settings to the program as data and refuses a program that built
another encoder, refuses an encoder with no module, and hands the readers
each device op's named scope."""
from pathlib import Path

import jax
import pytest

from bench import common, traces, train_cell
from bench.tests.conftest import tiny

TEST_ENCODERS = Path(__file__).resolve().parent / "encoders"


def test_a_non_mlp_encoder_needs_only_its_module(harness, monkeypatch):
    """The program's toy attention block against a test-only module. The
    program's spec does not carry ``enc_type`` from the trainer's
    arguments yet, so the test stands in for that one line of plumbing;
    ``n_layers`` 3 because the block's init draws from key 4 (and keeps
    this spec apart from the MLP specs of other tests)."""
    from repro.core.encoders import EncoderConfig
    from repro.core.federation_sharded import ShardedFedSpec

    monkeypatch.setattr(common, "ENCODERS", TEST_ENCODERS)
    monkeypatch.setattr(ShardedFedSpec, "ecfg", property(
        lambda self: EncoderConfig(d_hidden=self.d_hidden,
                                   n_layers=self.n_layers,
                                   enc_type="transformer")))
    rc, res = harness("mimic_cxr.train", enc_type="transformer", n_layers=3)
    assert rc == 0 and res["correct"], res["compared"]


@pytest.mark.parametrize("built, message", [
    ({"enc_type": "transformer"}, "enc_type to 'mlp'.*'transformer'"),
    ({"n_layers": 3}, "n_layers to 2.*3")])
def test_the_program_must_build_the_configured_encoder(harness, monkeypatch,
                                                       built, message):
    """An MLP configuration, and a program whose spec reports another
    encoder setting: set-up refuses it before it makes the round state."""
    from repro.core.encoders import EncoderConfig
    from repro.core.federation_sharded import ShardedFedSpec

    monkeypatch.setattr(ShardedFedSpec, "ecfg", property(
        lambda self: EncoderConfig(**dict(dict(d_hidden=self.d_hidden,
                                               n_layers=self.n_layers,
                                               enc_type="mlp"), **built))))
    with pytest.raises(ValueError, match=message):
        harness("mimic_cxr.train")


@pytest.mark.parametrize("settings", [
    {"n_heads": 8}, {"enc_type": "transformer", "n_layers": 3}])
def test_encoder_settings_reach_the_program_as_data(settings):
    """Each ``EncoderConfig`` setting that a configuration sets goes into
    the trainer's arguments, so a new encoder's settings need no edit."""
    info = tiny("mimic_cxr.train", **settings)
    args = train_cell._args(info["config"], info["traffic"], "store", 1)
    for key, value in dict(settings, d_hidden=16).items():
        assert getattr(args, key) == value, key
    assert "kind" not in common.encoder_args(info["config"])


def test_an_encoder_without_a_module_fails_in_setup(harness):
    with pytest.raises(FileNotFoundError, match="encoders/no_such_net.py"):
        harness("mimic_cxr.train", enc_type="no_such_net")


def test_every_encoder_module_has_the_contract():
    for name in ("mlp",):
        mod = common.encoder({"enc_type": name})
        for fn in ("init", "apply", "forward_flops", "train_flops", "n_params"):
            assert callable(getattr(mod, fn)), (name, fn)
    assert common.encoder({}) is common.encoder({"enc_type": "mlp"})


HLO = """HloModule jit_round_fn, is_scheduled=true

%fused_computation.5 (param_0: f32[8,16]) -> f32[8,16] {
  %param_0 = f32[8,16]{1,0} parameter(0)
  ROOT %tanh.1 = f32[8,16]{1,0} tanh(%param_0), metadata={op_name="jit(round_fn)/unimodal/jvp(vmap())/tanh"}
}

ENTRY %main.9 (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16]{1,0} parameter(0)
  %fusion.5 = f32[8,16]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(round_fn)/aggregate/score/vmap()/dot_general" stack_frame_id=3}
  %convolution.2 = f32[8,16]{1,0} convolution(%fusion.5), metadata={op_type="dot" op_name="jit(round_fn)/transpose(jvp(vmap(a/b)))/unimodal/jit(_where)/select_n"}
  %copy.7 = f32[8,16]{1,0} copy(%convolution.2)
  ROOT %add.3 = f32[8,16]{1,0} add(%copy.7, %p), metadata={op_name="jit(round_fn)/add"}
}
"""


def test_op_scopes_map_each_op_to_its_named_scope():
    labels = ["jit_round_fn:fusion.5 fusion f32[8,16]",
              "jit_round_fn:convolution.2 convolution f32[8,16]",
              "jit_round_fn:copy.7 copy f32[8,16]",
              "jit_round_fn:add.3 add f32[8,16]",
              "jit_other:fusion.5 fusion f32[8,16]"]
    scopes = traces.op_scopes(HLO, labels)
    assert [traces.scope_of(lb, scopes) for lb in labels] == [
        "aggregate/score", "unimodal", None, None, None]
    assert set(scopes) == {lb.split(" ")[0] for lb in labels}


def test_score_device_time_reads_the_scoring_ops_per_round():
    import types

    ms = 1_000_000
    ops = {"/device:TPU:0": [
        ("jit_round_fn:fusion.5 fusion f32[8,16]", 0, 4 * ms),
        ("jit_round_fn:fusion.5 fusion f32[8,16]", 2 * ms, 6 * ms),
        ("jit_round_fn:convolution.2 convolution f32[8,16]", 6 * ms, 9 * ms)]}
    scopes = {"jit_round_fn:fusion.5": "aggregate/score/inner",
              "jit_round_fn:convolution.2": "unimodal"}
    read = common.reader("train_score_device_ms")
    run = types.SimpleNamespace(ops=ops, op_scopes=scopes, rounds=2)
    assert read(run) == pytest.approx(3.0)  # 0-6 ms over two rounds
    run.op_scopes = {"jit_round_fn:convolution.2": "unimodal"}
    assert read(run) is None
