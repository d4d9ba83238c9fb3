"""Config flag-surface parity with the reference CLI (src/options.py:4-74)."""

import pytest

from defending_against_backdoors_with_robust_learning_rate_tpu.config import (
    Config, args_parser)


def test_defaults_match_reference():
    c = Config()
    # reference defaults, src/options.py:7-71
    assert c.data == "fmnist"
    assert c.num_agents == 10
    assert c.agent_frac == 1
    assert c.num_corrupt == 0
    assert c.rounds == 200
    assert c.aggr == "avg"
    assert c.local_ep == 2
    assert c.bs == 256
    assert c.client_lr == 0.1
    assert c.client_moment == 0.9
    assert c.server_lr == 1
    assert c.base_class == 5       # quirk: README says 1, code says 5
    assert c.target_class == 7
    assert c.poison_frac == 0.0
    assert c.pattern_type == "plus"
    assert c.robustLR_threshold == 0
    assert c.clip == 0
    assert c.noise == 0
    assert c.top_frac == 100
    assert c.snap == 1


def test_server_lr_forced_unless_sign():
    # src/federated.py:23
    assert Config(server_lr=5.0, aggr="avg").effective_server_lr == 1.0
    assert Config(server_lr=5.0, aggr="comed").effective_server_lr == 1.0
    assert Config(server_lr=5.0, aggr="sign").effective_server_lr == 5.0


def test_cli_parses_reference_command_line():
    # the canonical fmnist attack+defense line (src/runner.sh:18)
    cfg = args_parser(
        "--data=fmnist --local_ep=2 --bs=256 --num_agents=10 --rounds=200 "
        "--num_corrupt=1 --poison_frac=0.5 --robustLR_threshold=4 "
        "--device=cuda:1".split())
    assert cfg.num_corrupt == 1 and cfg.poison_frac == 0.5
    assert cfg.robustLR_threshold == 4
    assert cfg.agents_per_round == 10


def test_agents_per_round_floor():
    # floor(K * C), src/federated.py:68
    assert Config(num_agents=3383, agent_frac=0.01).agents_per_round == 33


@pytest.mark.parametrize("flag", ["--use_pallas", "--agg_layout=bucket",
                                  "--train_layout=megabatch"])
def test_removed_flags_are_refused(flag, capsys):
    """PR 29 took these three forks out with their flags: the parser
    refuses them as it refuses any flag it does not know."""
    with pytest.raises(SystemExit) as e:
        args_parser([flag])
    assert e.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
