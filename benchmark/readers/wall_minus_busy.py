"""Host wall of the traced window (first job's start to last job's end) minus
the device's busy time inside it, in ms a job: upload, Python, dispatch, fetch
and unpack that no device work covers. args: none."""


def read(ctx: dict, args: dict):
    return (ctx["span"] - ctx["trace"].busy_s) * 1e3 / ctx["jobs"]
