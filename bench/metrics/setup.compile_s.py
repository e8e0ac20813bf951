"""Election and compile: seconds the server spent building, tracing,
compiling or loading every bucket program and running it once
(``SolServer.stats["compile_s"]``, from its ``sol.compile`` spans), at the
window's close."""


def read(run):
    server = run.cell.server
    seconds = server.stats.get("compile_s") if server is not None else None
    return seconds or None
