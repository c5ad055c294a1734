"""Model families.  A family is a module of this package: it owns its
config class and its parameter tree (``init_params`` /
``init_params_device``), names its partition-rule table
(``PARTITION_RULES``, a table of ``sharding/rules.py``), says whether it
is a causal LM (``CAUSAL_LM``), and — a causal one — supplies
``cache_kind`` and ``serving_forward`` for the serving engine
(docs/serving.md §Model families).  ``_CONFIG_FAMILIES`` below is the one
table from a config class to its family: the engines and the rule
engine resolve through :func:`family_of`."""
from __future__ import annotations

import importlib
from typing import Any, Optional

# config class name (anywhere in the MRO) -> module of this package
_CONFIG_FAMILIES = {"GPT2Config": "gpt2", "BertConfig": "bert", "DeepseekV2Config": "deepseek_v2",
                    "SolarOpen2Config": "solar_open2", "ZayaConfig": "zaya", "KeyeConfig": "keye",
                    "GigaChat35Config": "gigachat35", "LagunaConfig": "laguna", "MiMoV2Config": "mimo_v2"}


def family_of(model_config: Any) -> Optional[Any]:
    """The family module of a model config object, or None for a config
    outside the built-in classes."""
    for klass in type(model_config).__mro__:
        name = _CONFIG_FAMILIES.get(klass.__name__)
        if name is not None:
            return importlib.import_module(f"{__name__}.{name}")
    return None
