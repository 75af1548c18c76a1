"""A configuration file of ``configs/`` as the program's model config.

The file holds the published config's keys (Hugging Face names) as run,
the name of its architecture's builder module (``"architecture"``) and
that of the plain reference module (``"reference"``), both beside it.
The builder's contract is in ``configs/qwen_dense.py``.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CONFIG_DIR = HERE / "configs"


def load(name: str, config_dir: Path = CONFIG_DIR) -> dict:
    with open(config_dir / f"{name}.json") as f:
        return json.load(f)


def architecture(c: dict):
    """The builder module named by the configuration."""
    if "architecture" not in c:
        raise ValueError(f"configuration {c.get('name')!r} names no builder "
                         "module under 'architecture'")
    return importlib.import_module(f"bench.configs.{c['architecture']}")


def to_model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    return architecture(c).to_model_config(c)


def reference(c: dict):
    """The plain reference module named by the configuration."""
    return importlib.import_module(f"bench.configs.{c['reference']}")


def param_count(c: dict) -> int:
    """Parameters one token's forward pass multiplies by, as run."""
    return architecture(c).param_count(c)
