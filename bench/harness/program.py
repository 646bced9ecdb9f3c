"""The system under test, reached only through its entry points.

A configuration file names the registered configuration it starts from
(``registered``), the fields it changes (``model_config``), and how the
program's fields must read afterwards: ``agrees`` maps a field to a key
of the file, ``program_fields`` to a value. A mismatch stops the run, so
the file states the model as it is run.
"""
from __future__ import annotations

from typing import Any, Dict

from harness.spec import SpecError


def file_value(conf: Dict[str, Any], path: str):
    node = conf
    for part in path.split("."):
        node = node[part]
    return node


def model_config(conf: Dict[str, Any], **override):
    """The program's ModelConfig for a configuration file. ``override``
    switches a path of the program on (the precision control uses
    ``dtype``/``param_dtype``); the agreement check then skips those."""
    from repro.configs import get_config

    cfg = get_config(conf["registered"]).replace(**conf["model_config"], **override)
    want = {k: file_value(conf, v) for k, v in conf["agrees"].items()}
    want.update(conf["program_fields"])
    for field, value in want.items():
        if field in override:
            continue
        have = getattr(cfg, field)
        if have != value:
            raise SpecError(f"{conf['name']}: the program's {field} is {have!r}, "
                            f"the file says {value!r}")
    return cfg


def build(cfg):
    from repro.models.model import build as build_model

    return build_model(cfg)
