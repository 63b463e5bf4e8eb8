"""Engine layer: seconds of host clock around plan and compile in set-up
(``compile_program`` and jit lower/compile for a sweep; every
``PlanServe.prefill``, which also runs one zero batch, for serving)."""


def read(ctx):
    return ctx.setup.get("compile_s")
