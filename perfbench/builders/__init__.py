"""One module per configuration family: build(cell, seed) -> a system with
setup(say), window(seconds, profiler, t_process_start), release(),
verify(say)."""
