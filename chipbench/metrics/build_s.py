"""Host clock around ``dslsh.build``, ending in ``block_until_ready``."""


def read(run):
    return run.build_s
