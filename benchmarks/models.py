"""From a configuration file to the program's model, by what the file
says: ``program.model`` and ``program.config`` name the class and its
dataclass of sizes (``module:attribute``), every field of that dataclass
which the file has under the same key is taken from the file, and
``program.reference`` names the module of its plain reference. A later
configuration of another architecture names other classes and another
reference; this file and the drivers do not change."""
from __future__ import annotations

import dataclasses
import importlib


def resolve(spec):
    module, _, attribute = spec.partition(":")
    return getattr(importlib.import_module(module), attribute)


def reference(config):
    """The configuration's plain reference: the module that
    ``program.reference`` names (see ``benchmarks/reference.py`` for what
    such a module gives)."""
    return importlib.import_module(config["program"]["reference"])


def program_config(config):
    """The program's own config object, at the sizes of the file."""
    cls = resolve(config["program"]["config"])
    given = {f.name: config[f.name] for f in dataclasses.fields(cls)
             if f.name in config}
    given["dtype"] = config["torch_dtype"]
    return cls(**given)


def build(config, seed):
    """The model with weights drawn from ``seed`` on the device by the
    program's own initialisers, in ONE jitted call: the constructor runs
    under a trace with the program's ``functional_key`` routing its draws
    to splits of the seed's key, so that XLA fuses every generator with
    its cast to the served type. Run eagerly, leaf by leaf, the same
    constructor took 17 s for 0.7 B parameters and 29.5 s for 2.05 B on
    the chip (PR 23): most of either cell's set-up."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.framework.random_seed import functional_key

    # the program's global generator too: a train step draws its keys there
    paddle.seed(seed)
    cls, sizes = resolve(config["program"]["model"]), program_config(config)
    made = []

    def construct(key):
        with functional_key(key):
            made.append(cls(sizes))
        return [p._data for _, p in made[-1].named_parameters()]

    arrays = jax.jit(construct)(jax.random.PRNGKey(seed))
    model = made[-1]
    for (_, p), a in zip(model.named_parameters(), arrays):
        p._data = a
    return model


def weights(model):
    """``{name: device array}`` as ``named_parameters()`` gives them."""
    return {name: p._data for name, p in model.named_parameters()}
