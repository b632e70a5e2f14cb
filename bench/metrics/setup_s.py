"""Process start to window start: model build, compilation (or loading it
from the cache), the program's own first session, the server's build."""


def read(run):
    return run.setup_s
