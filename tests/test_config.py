import pytest

from r2d2_tpu.config import Config, apex_epsilon, parse_overrides


def test_defaults_match_reference():
    cfg = Config()
    assert cfg.sequence.seq_len == 55
    assert cfg.replay.capacity == 500_000
    assert cfg.seqs_per_block == 40
    assert cfg.num_blocks == 1250
    assert cfg.num_sequences == 50_000
    assert cfg.env.obs_shape == (4, 84, 84)


def test_replace_dotted():
    cfg = Config().replace(**{"replay.capacity": 4000, "actor.num_actors": 8})
    assert cfg.replay.capacity == 4000
    assert cfg.actor.num_actors == 8
    # untouched sections preserved
    assert cfg.optim.lr == 1e-4


def test_parse_overrides_types():
    cfg = parse_overrides(
        Config(),
        ["--optim.lr=0.001", "--network.use_double=true", "--replay.batch_size=32"],
    )
    assert cfg.optim.lr == pytest.approx(1e-3)
    assert cfg.network.use_double is True
    assert cfg.replay.batch_size == 32


def test_parse_overrides_rejects_unknown():
    with pytest.raises(SystemExit):
        parse_overrides(Config(), ["--nope.lr=1"])
    with pytest.raises(SystemExit):
        parse_overrides(Config(), ["--optim.nope=1"])


def test_apex_epsilon_ladder():
    # eps_i = 0.4 ** (1 + 7*i/(N-1)): ref train.py:16-18
    n = 10
    eps = [apex_epsilon(i, n, 0.4, 7.0) for i in range(n)]
    assert eps[0] == pytest.approx(0.4)
    assert eps[-1] == pytest.approx(0.4**8)
    assert all(a > b for a, b in zip(eps, eps[1:]))
    assert apex_epsilon(0, 1, 0.4, 7.0) == pytest.approx(0.4)


def test_invalid_sizes_rejected():
    with pytest.raises(ValueError):
        Config().replace(**{"sequence.learning_steps": 15})  # 400 % 15 != 0
    with pytest.raises(ValueError):
        Config().replace(**{"replay.capacity": 500_100})


def test_bad_numeric_override_is_friendly():
    with pytest.raises(SystemExit):
        parse_overrides(Config(), ["--replay.batch_size=abc"])
    with pytest.raises(SystemExit):
        # python-tuple syntax is rejected with the triple-syntax hint
        parse_overrides(Config(), ["--network.conv_layers=((16,4,2),)"])


def test_conv_layers_cli_override():
    """Conv pyramids are CLI-settable as ';'-joined triples — needed to run
    small-frame configs (the Nature pyramid shrinks a 32x32 frame to 0) from
    the command line."""
    cfg = parse_overrides(Config(), ["--network.conv_layers=8,4,2;16,3,1"])
    assert cfg.network.conv_layers == ((8, 4, 2), (16, 3, 1))
    with pytest.raises(SystemExit):
        parse_overrides(Config(), ["--network.conv_layers=8,4;16,3,1"])


# ---------------------------------------------------------------------------
# retired options (config.py _RETIRED_FIELDS): an old config keeps loading
# where it meant what the code now always does, and is refused where it
# asked for a path that is gone

RETIRED_AT_OLD_DEFAULT = [
    ("network", "space_to_depth", "off"),
    ("network", "pallas_lstm", "off"),
    ("network", "pallas_lstm_block", 1),
    ("network", "pallas_lstm_interpret", False),
    ("optim", "pallas_decode_layout", "planar"),
    ("optim", "fused_double_unroll", "off"),
]


@pytest.mark.parametrize("section,field,old_default", RETIRED_AT_OLD_DEFAULT)
def test_retired_field_at_old_default_is_dropped(section, field, old_default):
    d = Config().to_dict()
    d[section][field] = old_default
    assert Config.from_dict(d) == Config()
    assert not hasattr(getattr(Config(), section), field)
    assert parse_overrides(
        Config(), [f"--{section}.{field}={old_default}"]) == Config()


@pytest.mark.parametrize("section,field,asked,legacy", [
    ("network", "space_to_depth", "on", True),
    ("network", "pallas_lstm", "auto", True),
    ("optim", "pallas_decode_layout", "nhwc", "NHWC"),
    ("optim", "fused_double_unroll", "on", "yes"),
])
def test_retired_path_selector_is_refused_by_name(section, field, asked,
                                                  legacy):
    for value in (asked, legacy):
        d = Config().to_dict()
        d[section][field] = value
        with pytest.raises(ValueError, match=f"{section}.{field}={value}.*"
                                             "removed in PR 29.*PERF.md"):
            Config.from_dict(d)
        with pytest.raises(ValueError, match=f"{section}.{field}={value}"):
            parse_overrides(Config(), [f"--{section}.{field}={value}"])
    # the legacy spellings of "off" (a bool in an old JSON, the CLI's
    # strings) still load
    if field != "pallas_decode_layout":
        for off in (False, "false", "0", "no", "OFF"):
            d = Config().to_dict()
            d[section][field] = off
            assert Config.from_dict(d) == Config()


@pytest.mark.parametrize("field,value", [
    ("pallas_lstm_block", 5), ("pallas_lstm_interpret", True)])
def test_retired_inert_field_is_dropped_at_any_value(field, value):
    d = Config().to_dict()
    d["network"][field] = value
    assert Config.from_dict(d) == Config()
    assert parse_overrides(
        Config(), [f"--network.{field}={value}"]) == Config()


def test_unknown_key_that_is_not_retired_still_fails():
    d = Config().to_dict()
    d["network"]["no_such_option"] = "off"
    with pytest.raises(TypeError, match="no_such_option"):
        Config.from_dict(d)
    with pytest.raises(SystemExit, match="no_such_option"):
        parse_overrides(Config(), ["--network.no_such_option=off"])


def test_parents_config_json_loads(tmp_path):
    """``tests/data/config_pr28.json`` is ``Config().to_json()`` of commit
    bc7f824, the last with the six options: it loads through ``from_json``
    and through the reader of a checkpoint's ``.config.json``."""
    import json
    import os
    import shutil

    from r2d2_tpu.runtime.checkpoint import load_checkpoint_config
    path = os.path.join(os.path.dirname(__file__), "data",
                        "config_pr28.json")
    with open(path) as f:
        text = f.read()
    stored = json.loads(text)
    for section, field, old_default in RETIRED_AT_OLD_DEFAULT:
        assert stored[section].pop(field) == old_default   # it carries them
    cfg = Config.from_json(text)
    # every value the file holds besides them arrived (JSON has no tuples;
    # a field added since is not the file's to hold)
    loaded = json.loads(cfg.to_json())

    def held(stored, loaded):
        """``loaded`` at the keys ``stored`` has, nested groups alike."""
        return {k: held(v, loaded[k]) if isinstance(v, dict) else loaded[k]
                for k, v in stored.items()}

    for section, fields in stored.items():
        assert held(fields, loaded[section]) == fields
    shutil.copy(path, tmp_path / "ckpt_7.config.json")
    assert load_checkpoint_config(str(tmp_path / "ckpt_7")) == cfg
