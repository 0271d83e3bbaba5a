"""Full-model save/load: architecture + weights in one directory.

Keras-era surface (``model.save(path)`` / ``models.load_model(path)``) on the
TPU-native stack: the reference's chief-checkpointing duty (README.md:51,
SURVEY.md §5.4) covers weights via ``training.checkpoint``; this adds the
architecture half so a model round-trips WITHOUT the constructing code.

Layers are frozen dataclasses, so a config is just the class name plus its
dataclass fields (layer-valued fields — Block.layers, Residual.main/shortcut
— recurse). Weights reuse the checkpoint format (chief-writes atomic npz);
``model.json`` carries architecture + compile metadata.

    model.save("saved/mnist")                 # chief writes, others no-op
    model2 = td.models.load_model("saved/mnist")
    model2.predict(x)                         # same params, same outputs
"""

from __future__ import annotations

import dataclasses
import json
import os
import pathlib
from typing import Optional

CONFIG_NAME = "model.json"


def _encode_value(v):
    from tpu_dist.models.layers import Layer
    from tpu_dist.parallel.sequence import RingAttention

    if isinstance(v, Layer):
        return {"__layer__": layer_config(v)}
    if isinstance(v, RingAttention):
        # Declarative attention spec (VERDICT r2 #8): plain data, mesh
        # resolved at call time from the restoring job's strategy scope.
        # An explicitly bound mesh is deliberately NOT saved — topology is
        # the restoring job's business, not the checkpoint's.
        return {"__attention__": {
            "class": "RingAttention",
            "config": {k: getattr(v, k)
                       for k in ("axis_name", "batch_axis", "scale",
                                 "kv_chunk")}}}
    if isinstance(v, (tuple, list)):
        return [_encode_value(e) for e in v]
    if callable(v):
        # e.g. MultiHeadAttention.attention_fn=partial(ring_attention, ...)
        raise TypeError(
            f"cannot serialize layer field holding a callable ({v!r}); "
            "use the declarative spec (RingAttention(axis_name=...)) for "
            "ring attention, or save_weights()/load_weights and rebuild "
            "the architecture in code for arbitrary attention_fn hooks")
    return v


def _decode_value(v):
    if isinstance(v, dict) and "__layer__" in v:
        return layer_from_config(v["__layer__"])
    if isinstance(v, dict) and "__attention__" in v:
        from tpu_dist.parallel.sequence import RingAttention

        spec = v["__attention__"]
        # Explicit allowlist, NOT getattr on the module: a crafted
        # model.json must not be able to instantiate arbitrary importable
        # classes with attacker-chosen kwargs (ADVICE r3).
        allowed = {"RingAttention": RingAttention}
        cls = allowed.get(spec["class"])
        if cls is None:
            raise ValueError(
                f"unknown attention spec class {spec['class']!r}")
        return cls(**spec["config"])
    if isinstance(v, list):
        return tuple(_decode_value(e) for e in v)
    return v


def layer_config(layer) -> dict:
    """{"class": ..., "config": {dataclass fields}} with nested layers
    encoded recursively."""
    fields = getattr(layer, "__dataclass_fields__", None)
    if fields is None:
        raise TypeError(
            f"cannot serialize non-dataclass layer {type(layer).__name__}; "
            "custom layers need dataclass fields to round-trip")
    cfg = {name: _encode_value(getattr(layer, name)) for name in fields}
    return {"class": type(layer).__name__, "config": cfg}


def layer_from_config(spec: dict):
    from tpu_dist.models import hybrid as hybrid_mod
    from tpu_dist.models import layers as layers_mod
    from tpu_dist.models import transformer as transformer_mod
    from tpu_dist.parallel import routed_experts as experts_mod

    cls = next((getattr(m, spec["class"]) for m in (
        layers_mod, transformer_mod, hybrid_mod, experts_mod)
        if hasattr(m, spec["class"])), None)
    # Layer subclasses only — the modules also import unrelated classes
    # (PartitionSpec, ...) that a crafted model.json must not reach.
    if (cls is None or not isinstance(cls, type)
            or not issubclass(cls, layers_mod.Layer)):
        raise ValueError(f"unknown layer class {spec['class']!r}")
    kwargs = {k: _decode_value(v) for k, v in spec["config"].items()}
    # JSON turns tuples (kernel_size, strides, pool_size...) into lists;
    # _decode_value already restored lists to tuples.
    return cls(**kwargs)


def _obj_config(obj) -> Optional[dict]:
    """{"class", "config"} from an op object's public attrs; None when an
    attr can't round-trip through JSON (e.g. a wrapped optax transform)."""
    from tpu_dist.ops.schedules import LearningRateSchedule

    cfg = {}
    for k, v in vars(obj).items():
        if k.startswith("_"):
            continue
        if isinstance(v, LearningRateSchedule):
            inner = _obj_config(v)
            if inner is None:
                return None
            v = {"__schedule__": inner}
        elif callable(v):
            return None
        elif isinstance(v, (list, tuple)):
            # NamedTuples (e.g. optax transforms) pass an isinstance-tuple
            # check while holding functions — require JSON scalars inside.
            if not all(isinstance(e, (int, float, str, bool, type(None)))
                       for e in v):
                return None
            v = list(v)
        elif not isinstance(v, (int, float, str, bool, type(None))):
            return None
        cfg[k] = v
    return {"class": type(obj).__name__, "config": cfg}


def _obj_from_config(spec: dict, module):
    import inspect

    from tpu_dist.ops import schedules as schedules_mod

    cls = getattr(module, spec["class"], None)
    if cls is None or not isinstance(cls, type):
        raise ValueError(
            f"unknown {module.__name__.rsplit('.', 1)[-1]} class "
            f"{spec['class']!r}")
    # Saved configs carry every public attr; constructors may accept only a
    # subset (e.g. a Loss sets self.name itself) — filter to the signature.
    accepted = set(inspect.signature(cls.__init__).parameters) - {"self"}
    kwargs = {}
    for k, v in spec["config"].items():
        if k not in accepted:
            continue
        if isinstance(v, dict) and "__schedule__" in v:
            v = _obj_from_config(v["__schedule__"], schedules_mod)
        elif isinstance(v, list):
            v = tuple(v)
        kwargs[k] = v
    return cls(**kwargs)


def _compile_config(model) -> Optional[dict]:
    """Loss/optimizer/metric identifiers, or None when any of them can't be
    serialized (load_model then returns an uncompiled model)."""
    if model.loss is None or model.optimizer is None:
        return None
    loss = _obj_config(model.loss)
    opt = _obj_config(model.optimizer)
    mets = [_obj_config(m) for m in model.metrics]
    if loss is None or opt is None or any(m is None for m in mets):
        return None
    return {"loss": loss, "optimizer": opt, "metrics": mets,
            "steps_per_execution": model.steps_per_execution}


def model_config(model) -> dict:
    from tpu_dist.models.model import Sequential

    if not isinstance(model, Sequential):
        raise TypeError(
            f"save/load supports Sequential models, got {type(model).__name__}")
    cfg = {
        "format": "tpu_dist.sequential.v1",
        "name": model.name,
        "input_shape": list(model.input_shape) if model.input_shape else None,
        "layers": [layer_config(l) for l in model.layers],
    }
    compiled = _compile_config(model)
    if compiled:
        cfg["compile"] = compiled
    return cfg


def save_model(model, directory) -> None:
    """Architecture (model.json, chief-only write) + weights (checkpoint
    step 0). Safe in multi-process jobs: non-chief processes write nothing,
    but every process MUST call this — checkpoint.save ends in a barrier,
    and when variables carry model-sharded (tensor-parallel) leaves it also
    allgathers them across processes, both collectives all peers join."""
    from tpu_dist.cluster import bootstrap
    from tpu_dist.models.model import Sequential
    from tpu_dist.training import checkpoint
    from tpu_dist.training.trainer import Trainer

    # Type check on EVERY process before any side effects: a chief-only
    # failure here would leave non-chief processes blocked at the
    # checkpoint barrier below.
    if not isinstance(model, Sequential):
        raise TypeError(
            f"save/load supports Sequential models, got {type(model).__name__}")
    directory = pathlib.Path(directory)
    if model._trainer is None:
        model._trainer = Trainer(model)
    model._trainer.ensure_variables()
    # Encode on EVERY process (not just the chief): an unserializable layer
    # field (e.g. a ring attention_fn) must raise everywhere, or non-chief
    # processes would block at the checkpoint barrier below.
    encoded = json.dumps(model_config(model), indent=2)
    if bootstrap.is_chief():
        directory.mkdir(parents=True, exist_ok=True)
        tmp = directory / f".{CONFIG_NAME}.tmp.{os.getpid()}"
        tmp.write_text(encoded)
        os.replace(tmp, directory / CONFIG_NAME)
    checkpoint.save(directory, model, step=0)


def load_model(directory, *, compile: bool = True):
    """Rebuild the Sequential from model.json, restore weights, and (by
    default) re-compile from the saved loss/optimizer/metric identifiers."""
    from tpu_dist.models.model import Sequential
    from tpu_dist.training import checkpoint

    directory = pathlib.Path(directory)
    spec = json.loads((directory / CONFIG_NAME).read_text())
    if spec.get("format") != "tpu_dist.sequential.v1":
        raise ValueError(f"unrecognized saved-model format in {directory}")
    model = Sequential(
        [layer_from_config(l) for l in spec["layers"]],
        input_shape=tuple(spec["input_shape"]) if spec["input_shape"]
        else None,
        name=spec.get("name", "sequential"))
    if compile and spec.get("compile"):
        from tpu_dist.ops import losses as losses_mod
        from tpu_dist.ops import metrics as metrics_mod
        from tpu_dist.ops import optimizers as optimizers_mod

        c = spec["compile"]
        model.compile(
            loss=_obj_from_config(c["loss"], losses_mod),
            optimizer=_obj_from_config(c["optimizer"], optimizers_mod),
            metrics=[_obj_from_config(m, metrics_mod)
                     for m in c.get("metrics", [])],
            steps_per_execution=c.get("steps_per_execution", 1))
    model.load_weights(directory, step=0)
    return model
