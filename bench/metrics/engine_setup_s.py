"""Engine set-up seconds: ``RunResult.setup_s`` of the run's first
``api.run`` call (data, model, clustering and placement in
``core/engine.py`` ``setup``), on the host clock of the program."""


def read(ctx):
    return ctx["engine_setup_s"]
