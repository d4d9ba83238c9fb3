"""`c2_token_eval` for a configuration whose model is `--arch=swa_moe`: the
same `run` (the engine's evaluation at the warm-up boundary against the
configuration's plain reference on the same parameters and sequences:
losses, token accuracy, the backdoor the file states, every (token, expert)
pair accounted for and each held expert's load), under a `contract` that
reads this model's keys: the layer kinds, the query heads by layer, the
window and the rotary parameters by kind. `c2_token_eval.contract` pins
`--arch=lfm2_moe` and `c2_mla_token_eval.contract` reads the latent model's
keys, and neither file is this PR's to edit; `run` reaches a model only
through its reference's functions, so it is taken as it is (three copies of
one `run` under three contracts: PERF.md section 7). What the window and
the rotary embedding by kind change shows in the losses: the reference masks
a full [T, T] matrix per layer kind, so a program that skipped a square
inside the band, or rotated a full layer at the sliding layers'
frequencies, would leave them. On the device beside the engine's
parameters: one sequence's activations, the largest one key-value head's
group of scores, 8 x 4096 x 4096 float32 (0.54 GB), and a held expert's
hidden layer for all tokens."""

from __future__ import annotations

from benchmark.checks import c2_token_eval as base

PHASE = base.PHASE
run = base.run


def contract(cfg, config) -> None:
    """The flags parse to the model, the cut, the backdoor and the tokens a
    round that the file states (`mfu_pct` multiplies by
    `examples_per_round`)."""
    from defending_against_backdoors_with_robust_learning_rate_tpu.models import (
        swa_moe)
    assert cfg.data == "tokens" and cfg.model_arch == "swa_moe"
    spec = swa_moe.spec_from_cfg(cfg)
    assert [src for src, _k, _h, _s in spec.layers] == config["layers_held"]
    assert len(spec.layers) == config["num_hidden_layers"]
    assert [k for _i, k, _h, _s in spec.layers] == config["layer_types"]
    assert [h for _i, _k, h, _s in spec.layers] == \
        config["num_attention_heads_per_layer"]
    assert ["sparse" if s else "dense" for _i, _k, _h, s in spec.layers] == \
        config["mlp_layer_types"]
    for key, have in (
            ("hidden_size", spec.hidden), ("vocab_size", spec.vocab_held),
            ("intermediate_size", spec.dense_ffn),
            ("moe_intermediate_size", spec.moe_ffn),
            ("shared_expert_intermediate_size", spec.shared_ffn),
            ("num_key_value_heads", spec.kv_heads),
            ("head_dim", spec.head_dim), ("sliding_window", spec.window),
            ("num_experts", spec.experts_held),
            ("expert_offset", spec.expert_offset),
            ("num_experts_per_tok", spec.top_k),
            ("moe_routed_scaling_factor", spec.routed_scale),
            ("rms_norm_eps", spec.norm_eps), ("seq_len", cfg.seq_len)):
        assert config[key] == have, key
    for kind, rope in spec.rope:
        stated = config["rope_parameters"][kind]
        assert rope.theta == stated["rope_theta"], kind
        assert rope.rotated == int(spec.head_dim
                                   * stated["partial_rotary_factor"]), kind
        assert (rope.yarn is not None) == (stated["rope_type"] == "yarn")
        if rope.yarn is not None:
            assert rope.yarn == (
                stated["factor"], stated["original_max_position_embeddings"],
                stated["beta_fast"], stated["beta_slow"]), kind
            assert rope.scale == stated["attention_factor"], kind
    assert config["published"]["num_experts"]["source"] == spec.n_experts
    bd = config["backdoor"]
    assert bd["target"] == cfg.target_class
    assert bd["trigger"] == list(range(spec.vocab_held - 3, spec.vocab_held))
    assert cfg.synth_val_size % cfg.eval_bs == 0      # no padded sequence
    assert (cfg.local_ep * cfg.synth_train_size * cfg.seq_len
            == config["examples_per_round"])
    for key in ("val_loss_rtol", "poison_loss_rtol", "acc_tokens",
                "pairs_rtol", "pairs_atol", "pairs_moved_share"):
        assert config["check"][key] > 0, key
