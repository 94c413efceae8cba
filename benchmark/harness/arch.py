"""The seam between the harness and one architecture (standard library).

A configuration file states ``"architecture": "<name>"``;
``benchmark/architectures/<name>/`` is a package of four modules with the
surface below (README.md, "An architecture that is not here").  The
harness names no tensor, no kind of layer and no published key: whatever
depends on the block goes through the package that the file names.

``load_shapes`` is for the parent and its readers, which never import JAX:
it imports ``keys`` and ``shapes`` only.
"""

from __future__ import annotations

import importlib
import inspect
import json
import os
import types
import zlib

ARCH_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "architectures"
)

# what the harness, its readers and calibrate.py call; whatever else a
# package has is its own
SURFACE = {
    "keys": ("program_overrides",),
    "shapes": ("decode_step_min_bytes",),
    "weights": ("make_decoder_params", "controls_for", "kv_only_controls"),
    "reference": ("forward_logits",),
}

# the keys of a configuration file that the harness reads itself; every
# other top-level key is a published key of the model, and the package's
# ``keys.program_overrides`` has to know it
HARNESS_KEYS = frozenset({
    "architecture", "serving", "corpus", "chips", "deployment", "assumed",
    "correct", "check", "kv_cache_bits", "weights_seed",
})


class ConfigError(ValueError):
    """A configuration file the harness cannot run, with the file (where
    known) and the key in the message."""


def stated_weights_seed(name: str) -> int:
    """The seed a configuration NAMED ``name`` draws its decoder weights
    from: the CRC-32 of the name, so that nobody chooses it by what it
    reads.  A deployment serves one set of weights for months; ``--seed``
    draws the traffic (README.md, "What the seed may draw")."""
    return zlib.crc32(name.encode("utf-8"))


def model_keys(conf: dict) -> dict:
    """The published keys of the file: what is not the harness's own."""
    return {k: v for k, v in conf.items() if k not in HARNESS_KEYS}


def _package(conf: dict, modules):
    name = conf.get("architecture")
    here = sorted(
        d for d in os.listdir(ARCH_DIR)
        if os.path.isfile(os.path.join(ARCH_DIR, d, "__init__.py"))
    )
    if name not in here:
        raise ConfigError(
            f'key "architecture": {name!r} is no package under '
            f"benchmark/architectures/ (there: {here})"
        )
    pkg = types.SimpleNamespace(name=name)
    for module in modules:
        mod = importlib.import_module(f"architectures.{name}.{module}")
        for fn in SURFACE[module]:
            if not callable(getattr(mod, fn, None)):
                raise ConfigError(
                    f"architectures/{name}/{module}.py has no {fn}()"
                )
        setattr(pkg, module, mod)
    return pkg


def load(conf: dict):
    """The four modules of the configuration's architecture, as
    ``.keys``, ``.shapes``, ``.weights``, ``.reference`` (imports JAX)."""
    return _package(conf, ("keys", "shapes", "weights", "reference"))


def routes(package) -> bool:
    """Whether the block of a loaded package (``load``) routes.  A package
    says so in one way: its ``reference.forward_logits`` takes ``routing``
    (README.md, "A block that routes").  ``correct`` then replays the
    program's expert choices in the reference and holds the choices
    themselves to ``router_choice_gap``."""
    params = inspect.signature(package.reference.forward_logits).parameters
    return "routing" in params


def load_shapes(conf: dict):
    """``.keys`` and ``.shapes`` alone: standard library, for the parent."""
    return _package(conf, ("keys", "shapes"))


def load_cell_config(path: str, overlay: str = "") -> dict:
    """The configuration file, with a test's overlay (tiny widths) merged
    over it when given.  An unknown architecture, or a published key its
    package does not map, is an error here, before anything is started; so
    is a file whose ``weights_seed`` is not the one its name states."""
    with open(path, encoding="utf-8") as f:
        conf = json.load(f)
    if overlay:
        with open(overlay, encoding="utf-8") as f:
            over = json.load(f)
        for key, value in over.items():
            if isinstance(value, dict) and isinstance(conf.get(key), dict):
                conf[key] = {**conf[key], **value}
            else:
                conf[key] = value
    try:
        load_shapes(conf).keys.program_overrides(conf)
    except ConfigError as e:
        raise ConfigError(f"{path}: {e}") from None
    name = os.path.basename(path).removesuffix(".json")
    stated = stated_weights_seed(name)
    if conf.get("weights_seed") != stated:
        raise ConfigError(
            f'{path}: key "weights_seed": {conf.get("weights_seed")!r} is '
            f"not the CRC-32 of the configuration's name, {stated} for "
            f"{name!r}"
        )
    return conf
