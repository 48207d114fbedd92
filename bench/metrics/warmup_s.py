"""Backend warm-up: seconds spent serving every plan at every wave size
before the window (compiles or persistent-cache loads), host clock."""


def read(run: dict):
    return run["phases"]["warmup_s"]
