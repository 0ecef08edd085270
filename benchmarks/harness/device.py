"""The device a result is stamped with, and the chip's published peaks."""
import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def stamp(jax, chips):
    """The first `chips` devices as JAX reports them."""
    devs = jax.devices()[:chips]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require(jax, chips):
    """stamp(), or NoAccelerator: a timing from the CPU backend says
    nothing about this system, and a cell cut to fewer chips than it
    asks for is another cell."""
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator(
            "JAX found no accelerator (platform 'cpu'); the benchmark "
            "measures the chip. A CPU rehearsal is an explicit --rehearse.")
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips, JAX sees {len(devs)}")
    peaks(devs[0].device_kind)
    return stamp(jax, chips)


def peaks(device_kind):
    """The row of peaks.json for `device_kind`.  A device that is not in
    the table is an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["peaks"]
    kind = device_kind.lower()
    for row in table:
        if row["match"] in kind:
            return row
    raise NoAccelerator(
        f"no peaks on record for device_kind {device_kind!r}: add a row "
        f"to {PEAKS_FILE} with its source")


def memory_peak_bytes(jax, chips):
    """Peak bytes on the fullest of the chips used.  On this runtime
    `peak_bytes_in_use` counts live arrays and `peak_bytes_reserved`
    follows what running executables reserved for temporaries (PERF.md,
    PR 21); a chip is as full as the larger of the two."""
    worst = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats()
        if stats is None:
            return 0
        worst = max(worst, stats.get("peak_bytes_in_use", 0),
                    stats.get("peak_bytes_reserved", 0))
    return int(worst)
