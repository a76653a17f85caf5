"""Set-up time (host clock): process start to the window's start,
weights, warm-up and compilation (or cache loads) included."""


def read(obs):
    return obs["setup_s"]
