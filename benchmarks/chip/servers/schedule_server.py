"""The program's ``ScheduleServer``, built from the configuration's
``serve`` block: ``options`` become its ``SolveOptions``; every other key
but ``server`` is passed to it as it stands."""


def make(config: dict):
    from repro.api import SolveOptions
    from repro.serve.server import ScheduleServer

    kw = dict(config["serve"])
    kw.pop("server")
    options = SolveOptions(**kw.pop("options", {}))
    return ScheduleServer(int(config["s"]), float(config["delta"]),
                          options=options, **kw)
