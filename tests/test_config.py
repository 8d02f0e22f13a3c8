"""Config parsing / schema validation (reference schema: config/ResNet50.yml:1-31)."""
import os

import pytest
import yaml

from pytorch_distributed_training_tpu.config_parsing import (
    get_cfg,
    get_serve_cfg,
    validate_cfg,
)

GOOD = {
    "dataset": {"name": "synthetic", "root": "/tmp/x", "n_classes": 10},
    "training": {
        "optimizer": {"name": "SGD", "lr": 0.1, "weight_decay": 1.0e-4, "momentum": 0.9},
        "lr_schedule": {"name": "multi_step", "milestones": [10, 20], "gamma": 0.1},
        "train_iters": 30,
        "print_interval": 5,
        "val_interval": 10,
        "batch_size": 8,
        "num_workers": 0,
        "sync_bn": True,
    },
    "validation": {"batch_size": 8, "num_workers": 0},
    "model": {"name": "ResNet18"},
}


@pytest.mark.quick
def test_roundtrip(tmp_path):
    p = tmp_path / "cfg.yml"
    p.write_text(yaml.safe_dump(GOOD))
    cfg = get_cfg(str(p))
    assert cfg["training"]["optimizer"]["name"] == "SGD"
    assert cfg["dataset"]["n_classes"] == 10
    # The dead validation: section must be *accepted* (parity with reference).
    assert cfg["validation"]["batch_size"] == 8


def test_reference_configs_validate():
    """Our shipped configs follow their schema exactly — training configs
    the reference schema, ``serve-*.yml`` the serving one."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg_dir = os.path.join(here, "config")
    names = sorted(n for n in os.listdir(cfg_dir) if n.endswith(".yml"))
    assert len(names) >= 8  # every shipped config is schema-validated
    for name in names:
        loader = get_serve_cfg if name.startswith("serve-") else get_cfg
        cfg = loader(os.path.join(cfg_dir, name))
        assert cfg["model"]["name"]


def test_missing_key_raises():
    import copy

    bad = copy.deepcopy(GOOD)
    del bad["training"]["sync_bn"]
    with pytest.raises(KeyError):
        validate_cfg(bad)

    bad = copy.deepcopy(GOOD)
    del bad["model"]
    with pytest.raises(KeyError):
        validate_cfg(bad)


def test_warmup_keys_accepted():
    import copy

    cfg = copy.deepcopy(GOOD)
    cfg["training"]["lr_schedule"].update(
        {"warmup_iters": 300, "warmup_mode": "linear", "warmup_factor": 0.3333}
    )
    validate_cfg(cfg)


def test_all_shipped_configs_validate_against_generated_schema():
    """pdt-analyze's config-schema pass infers the accepted key/type
    surface from the parse_*/from_config sites and statically validates
    the shipped YAMLs: no unknown keys in closed sections, no type
    mismatches, no dead allow-set keys.  Pin all 16 configs clean."""
    import pathlib

    from pytorch_distributed_training_tpu.analysis import core
    from pytorch_distributed_training_tpu.analysis.configschema import ConfigSchemaPass

    repo = pathlib.Path(__file__).parent.parent
    pkg = repo / "pytorch_distributed_training_tpu"
    assert len(list((repo / "config").glob("*.yml"))) == 20
    ctx = core.AnalysisContext(package_root=pkg, repo_root=repo)
    findings = ConfigSchemaPass().run(core.collect_modules(pkg, repo), ctx)
    assert findings == [], "\n".join(f.format() for f in findings)
