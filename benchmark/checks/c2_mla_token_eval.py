"""`c2_token_eval` for a configuration whose model is `--arch=mla_moe`: the
same `run` (the engine's evaluation at the warm-up boundary against the
configuration's plain reference on the same parameters and sequences:
losses, token accuracy, the backdoor the file states, every (token, expert)
pair accounted for and each held expert's load), under a `contract` that
reads this model's keys. `c2_token_eval.contract` pins `--arch=lfm2_moe` and
that model's keys (`layer_types`, `conv_L_cache`, `num_experts`, ...), and
the file is not this PR's to edit; `run` reaches a model only through its
reference's functions, so it is taken as it is. Eval runs the main model
only: the multi-token-prediction module is in no number compared here
(`c2_token_round` compares the loss and the update it shapes). On the
device and in seconds: as `c2_token_eval` states, with this reference's
scores 32 x 2048 x 2048 float32 twice (the two parts of a latent key)."""

from __future__ import annotations

from benchmark.checks import c2_token_eval as base

PHASE = base.PHASE
run = base.run


def contract(cfg, config) -> None:
    """The flags parse to the model, the cut, the backdoor and the tokens a
    round that the file states (`mfu_pct` multiplies by
    `examples_per_round`)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        mla_moe)
    assert cfg.data == "tokens" and cfg.model_arch == "mla_moe"
    spec = mla_moe.spec_from_cfg(cfg)
    assert [src for src, _s in spec.layers] == config["layers_held"]
    assert len(spec.layers) == config["num_hidden_layers"]
    assert sum(1 for _s, sparse in spec.layers if not sparse) == \
        min(config["first_k_dense_replace"], len(spec.layers))
    for key, have in (
            ("hidden_size", spec.hidden), ("vocab_size", spec.vocab_held),
            ("intermediate_size", spec.dense_ffn),
            ("moe_intermediate_size", spec.moe_ffn),
            ("num_attention_heads", spec.heads),
            ("q_lora_rank", spec.q_rank), ("kv_lora_rank", spec.kv_rank),
            ("qk_nope_head_dim", spec.nope_dim),
            ("qk_rope_head_dim", spec.rope_dim),
            ("v_head_dim", spec.v_dim),
            ("n_routed_experts", spec.experts_held),
            ("expert_offset", spec.expert_offset),
            ("num_experts_per_tok", spec.top_k),
            ("routed_scaling_factor", spec.routed_scale),
            ("num_nextn_predict_layers", spec.mtp_depth),
            ("mtp_loss_weight", spec.mtp_weight),
            ("rms_norm_eps", spec.norm_eps), ("rope_theta", spec.rope_theta),
            ("seq_len", cfg.seq_len)):
        assert config[key] == have, key
    assert spec.shared_ffn == (config["n_shared_experts"]
                               * config["moe_intermediate_size"])
    assert config["published"]["n_routed_experts"]["source"] == spec.n_experts
    assert (config["published"]["num_hidden_layers"]["source"]
            == spec.mtp_src_layer)
    bd = config["backdoor"]
    assert bd["target"] == cfg.target_class
    assert bd["trigger"] == list(range(spec.vocab_held - 3, spec.vocab_held))
    assert cfg.synth_val_size % cfg.eval_bs == 0      # no padded sequence
    assert (cfg.local_ep * cfg.synth_train_size * cfg.seq_len
            == config["examples_per_round"])
    for key in ("val_loss_rtol", "poison_loss_rtol", "acc_tokens",
                "pairs_rtol", "pairs_atol", "pairs_moved_share"):
        assert config["check"][key] > 0, key
