"""Frontend jit cache: variants compiled after the window opened
(``JitAuditor`` violations; should read 0). Moves ``goodput_rps``."""


def read(run):
    return float(len(run.sys_.auditor.violations))
