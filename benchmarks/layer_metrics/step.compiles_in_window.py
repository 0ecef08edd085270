"""Layer: step.  Step executables compiled inside the window
(`generation.decode_compiles_total`); anything but 0 voids the tails."""


def read(obs):
    c = obs["result"].get("counters")
    if c is None:
        return None
    return float(c.get("generation.decode_compiles_total", 0))
