"""Layer: benchmark.  Seconds of set-up spent building the model and its
weights from the seed."""


def read(obs):
    return obs["clock"].get("weights_s")
