"""Small shared helpers."""

from typing import Optional, Sequence


def round_up(n: int, quantum: int) -> int:
    """Smallest multiple of ``quantum`` >= n."""
    return -(-n // quantum) * quantum


def host_seed_from_rng(rng, host_seed: Optional[int] = None) -> int:
    """Numpy seed for a host-side param init.

    Pass ``host_seed`` (the integer the caller built its PRNGKey from)
    whenever it is known: the fallback reads ``jax.random.key_data(rng)``
    — a device→host fetch init has no other need for.  For a fresh
    ``PRNGKey(s)`` the two paths agree (threefry key data is the seed
    packed into two uint32s), so passing the seed changes no generated
    values — it only skips the fetch."""
    if host_seed is not None:
        return int(host_seed) & 0x7FFFFFFF
    import jax

    return int(jax.random.key_data(rng).ravel()[-1]) & 0x7FFFFFFF


def pick_bucket(value: int, buckets: Sequence[int]) -> int:
    """Smallest bucket >= value, else the largest bucket."""
    for b in buckets:
        if value <= b:
            return b
    return buckets[-1]


def compiled_memory_stats(lowered_compiled) -> Optional[dict]:
    """``memory_analysis()`` of an AOT-compiled jax program as plain ints,
    or None when the backend provides no analysis.

    Lives here (not in ``analysis/``) because both the SERVING layer
    (``GenerateEngine.decode_memory_analysis``, the telemetry sampler's
    HBM probe) and the audit tooling
    (``analysis/compile_audit.py`` gates ``compile_budget.json``) read
    the same accounting — engines must never import the lint tree.

    ``peak_bytes`` = argument + output + temp − alias: the working set
    resident during a dispatch, with donation aliases (in-place cache /
    table updates) not double-counted."""
    try:
        ma = lowered_compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
        ("alias_bytes", "alias_size_in_bytes"),
    ):
        try:
            out[key] = int(getattr(ma, attr))
        except Exception:
            out[key] = 0
    out["peak_bytes"] = max(
        0,
        out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"],
    )
    return out
