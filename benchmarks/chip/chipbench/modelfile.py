"""A configuration file as the model that the trial builds.

A file under ``configs/`` states its model by ``ModelConfig`` field name.
Every key that is a field is passed to ``PopulationTrial(model_overrides=...)``
and replaces the value of the program's preset.  The other keys describe the
file (its source, the cut, the control) and are passed nowhere.  So that no
width of the smoke preset survives under a file of published widths, and no
key is taken for a width that nothing runs, a file is refused where

- a key is neither a field, nor a descriptive key, nor listed under
  ``source_keys`` (keys copied from the published config.json, under its
  names; those that name a field must agree with the value that runs);
- it uses a layer kind that ``LAYER_FIELDS`` does not know, or leaves out a
  field that one of its layer kinds uses;
- the trial, built from the preset and the file, runs another layer pattern
  than the file states: the FLOP count (``flops.py``) reads only the file.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

DESCRIPTIVE_KEYS = frozenset({
    "source", "source_config", "preset", "reference", "control", "published",
    "reduced", "departures", "assumed", "grad_dtype", "matmul_precision",
    "deployment", "smoke", "source_keys"})

# a file that leaves these out states one attention + dense layer, repeated
PATTERN_DEFAULTS = {"pattern": (("attn", "dense"),), "prefix_pattern": ()}

# the fields that every file names, and those each layer kind adds
COMMON_FIELDS = ("d_model", "n_layers", "vocab_size")
LAYER_FIELDS = {
    "attn": ("n_heads", "n_kv_heads", "head_dim"),
    "attn_mla": ("n_heads", "n_kv_heads", "head_dim", "kv_lora_rank",
                 "q_lora_rank", "rope_head_dim"),
    "dense": ("d_ff", "activation"),
    "moe": ("n_experts", "n_shared_experts", "moe_top_k", "moe_d_ff",
            "capacity_factor", "activation"),
}

# the field that a published config.json's own name for it stands for
SOURCE_NAMES = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "qk_nope_head_dim": "head_dim",
    "v_head_dim": "head_dim",
    "qk_rope_head_dim": "rope_head_dim",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "n_routed_experts": "n_experts",
    "num_experts": "n_experts",
    "num_local_experts": "n_experts",
    "num_experts_per_tok": "moe_top_k",
    "num_shared_experts": "n_shared_experts",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

Layer = Tuple[str, str]


class ConfigError(ValueError):
    pass


def _tuples(v: Any) -> Any:
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def stated_pattern(config: Dict[str, Any], key: str) -> Tuple[Layer, ...]:
    return _tuples(config.get(key, PATTERN_DEFAULTS[key]))


def layers(config: Dict[str, Any]) -> List[Layer]:
    """Every layer's (mixer, ffn), first to last: the prefix, then the
    pattern repeated over the layers that are left."""
    prefix = stated_pattern(config, "prefix_pattern")
    pattern = stated_pattern(config, "pattern")
    body = config["n_layers"] - len(prefix)
    if body < 0 or body % len(pattern):
        raise ConfigError(f"{config.get('name')}: {config['n_layers']} layers "
                          f"are not a prefix of {len(prefix)} and whole "
                          f"periods of {len(pattern)}")
    return list(prefix) + list(pattern) * (body // len(pattern))


def published(config: Dict[str, Any], field: str) -> Any:
    """The source's value of ``field``: the first of its ``reduced`` entry
    (under the field's name or the source's), else the value as run."""
    for key, (was, _) in config.get("reduced", {}).items():
        if field in (key, SOURCE_NAMES.get(key)):
            return was
    return config[field]


def _model_fields():
    from repro.configs.base import ModelConfig

    return {f.name for f in dataclasses.fields(ModelConfig)}


def model_overrides(config: Dict[str, Any], control: bool,
                    where: str = "") -> Dict[str, Any]:
    """The ``ModelConfig`` fields that ``config`` states, with its control's
    where ``control``; raises ``ConfigError`` where the file is refused."""
    where = where or f"configuration {config.get('name')!r}"
    fields = _model_fields()
    copied = set(config.get("source_keys", ()))
    unknown = sorted(set(config) - fields - DESCRIPTIVE_KEYS - copied)
    if unknown:
        raise ConfigError(
            f"{where}: {', '.join(unknown)}: neither a ModelConfig field, nor "
            f"a descriptive key, nor listed under source_keys")
    over = {k: _tuples(v) for k, v in config.items() if k in fields}
    if control:
        bad = sorted(set(config["control"]) - fields)
        if bad:
            raise ConfigError(f"{where}: control sets {', '.join(bad)}, "
                              f"which is no ModelConfig field")
        over.update({k: _tuples(v) for k, v in config["control"].items()})
    _check_layers(config, over, copied, where)
    return over


def _check_layers(config, over, copied, where) -> None:
    from repro.configs import get_smoke_config

    prefix = stated_pattern(config, "prefix_pattern")
    pattern = stated_pattern(config, "pattern")
    kinds = {kind for layer in prefix + pattern for kind in layer}
    unknown = sorted(kinds - set(LAYER_FIELDS))
    if unknown:
        raise ConfigError(f"{where}: layer kind {', '.join(unknown)} has no "
                          f"entry in the harness's table of fields")
    needed = set(COMMON_FIELDS).union(*(LAYER_FIELDS[k] for k in kinds))
    missing = sorted(needed - set(config))
    if missing:
        raise ConfigError(f"{where}: leaves out {', '.join(missing)}, which "
                          f"its layers use")
    built = dataclasses.replace(get_smoke_config(config["preset"]), **over)
    if (built.prefix_pattern, built.pattern) != (prefix, pattern):
        raise ConfigError(
            f"{where}: states prefix_pattern {prefix} and pattern {pattern}, "
            f"but the trial builds {built.prefix_pattern} and {built.pattern}")
    for key in sorted(copied & set(config)):
        field = SOURCE_NAMES.get(key)
        if field and config[key] != getattr(built, field):
            raise ConfigError(f"{where}: {key} is {config[key]!r}, but the "
                              f"trial runs {field} = {getattr(built, field)!r}")


def check(config: Dict[str, Any], where: str) -> None:
    """Refuse the file now, with and without its control, rather than when a
    trial is built."""
    model_overrides(config, control=False, where=where)
    if "control" in config:
        model_overrides(config, control=True, where=where)
