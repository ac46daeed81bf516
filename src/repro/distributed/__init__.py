from repro.distributed.straggler import StragglerWatchdog  # noqa: F401
