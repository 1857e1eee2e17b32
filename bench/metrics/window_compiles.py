"""Engine admission: programs that reached XLA's backend inside the
measured window, compiled or loaded from the persistent cache, counted by
the compile clock (should read 0: every admission shape is warmed in
set-up). Moves ttft_p50_ms."""


def read(run):
    return run.window_compiles
