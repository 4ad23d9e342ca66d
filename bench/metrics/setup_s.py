"""setup_s: seconds from process start to the start of the window
(imports, data generation, the build, warm-up, compile or cache loads)."""


def read(ctx):
    return ctx["setup_s"]
