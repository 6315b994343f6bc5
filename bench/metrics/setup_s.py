"""Set-up seconds: from the start of the process to the window's open
(imports, device start, plan and chain or solver build, inputs made on
the device, warm-up and any compile), on the host's clock."""


def read(run):
    return run.setup_s
