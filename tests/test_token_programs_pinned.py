"""The lowered text of the programs that share code with the linear-attention
model (`models/gdn_moe.py`), pinned by sha256: the `round` program of each of
the three other token models and the ResNet's `round` and `eval_val`, each at
a toy size, as the planner builds them on the CPU. The linear-attention model
reaches `token_ops.sparse_ffn` through its softmax selection and
`token_ops.shared_expert` through its sigmoid gate; neither may change a
single operation of a program that asks for neither. Nothing compiles or
runs. A change that alters one of these programs on purpose writes the new
digest here, and says why."""

import hashlib
import os

import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config)
from defending_against_backdoors_with_robust_learning_rate_tpu.models.registry import (
    get_model)
from defending_against_backdoors_with_robust_learning_rate_tpu.utils import (
    compile_cache as cc)

DATA = os.path.join(os.path.dirname(__file__), "data")
TOKENS = dict(data="tokens", lm_experts_held=4, lm_expert_offset=2,
              lm_vocab_held=96, num_agents=4, bs=2, local_ep=2,
              synth_train_size=8, synth_val_size=4, eval_bs=2, num_corrupt=1,
              poison_frac=0.5, robustLR_threshold=3, agent_chunk=1,
              remat=True, agg_path="fold", tensorboard=False,
              compile_cache=False, data_dir="/nonexistent")
CONFIGS = {
    "lfm2_moe": Config(arch="lfm2_moe",
                       lm_config=os.path.join(DATA, "lm_tiny.json"),
                       lm_layers="1,2,3,4", seq_len=16, **TOKENS),
    "mla_moe": Config(arch="mla_moe",
                      lm_config=os.path.join(DATA, "mla_tiny.json"),
                      lm_layers="0,1,2", seq_len=16, **TOKENS),
    "swa_moe": Config(arch="swa_moe",
                      lm_config=os.path.join(DATA, "swa_tiny.json"),
                      lm_layers="0,1,2,3,4", seq_len=32, **TOKENS),
    "resnet9": Config(data="cifar10", arch="resnet9", num_agents=4, bs=16,
                      local_ep=1, agent_chunk=2, remat=True, num_corrupt=1,
                      synth_train_size=128, synth_val_size=64, eval_bs=64,
                      rounds=1, snap=1, tensorboard=False,
                      data_dir="/nonexistent", compile_cache=False,
                      heartbeat=False),
}
# sha256 of each program's lowered text, read from the tree before the
# linear-attention model came in
PINNED = {
    ("lfm2_moe", "round"):
        "dc01cbdaf92589c0110c787860baf43ef25a7cdc2857134550a041278130fc3c",
    ("mla_moe", "round"):
        "91effc9740c82935b11e381e9b5f32b9a2b68135b705f390e9fcbeea10f386fc",
    ("swa_moe", "round"):
        "eaf7d3a282edfe8e3e09483480ae09882f885a0ca54a2739803efdf3db43789e",
    ("resnet9", "round"):
        "035346d1d0d5ec0c0ced41e7c5c451566973574e3742ab1d4175f3916ab9890b",
    ("resnet9", "eval_val"):
        "15dd110228c395770e27aa5b77ceedace62f2bfd931d1d18667ac775b6bbdd79",
}


def lowered_text(cfg, family):
    """The lowered text of one program family as the engine gets it:
    remat policy from `resolved_remat`, model from `get_model`, program from
    the planner."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.data.registry import (
        get_federated_data)
    from defending_against_backdoors_with_robust_learning_rate_tpu.fl.common import (
        make_normalizer)
    fed = get_federated_data(cfg)
    cfg = cfg.replace(remat_policy=cc.resolved_remat(cfg, fed).policy)
    model = get_model(cfg.data, cfg.model_arch, cfg.dtype, remat=cfg.remat,
                      remat_policy=cfg.remat_policy, cfg=cfg)
    norm = make_normalizer(fed.mean, fed.std, fed.raw_is_normalized)
    spec, = [s for s in cc.plan_programs(cfg, model, norm, fed)
             if s.family == family]
    return cc.lower_program(spec.jit_obj, spec.example_args).as_text()


@pytest.mark.parametrize("arch,family", sorted(PINNED))
def test_the_program_lowers_to_the_pinned_text(arch, family):
    text = lowered_text(CONFIGS[arch], family)
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[arch, family]
