"""setup_s: seconds from the start of the process to the window's open
(imports, engine, queue, warm-up and every compile it makes)."""


def read(run):
    return run.setup_s
