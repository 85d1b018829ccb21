"""Keep pytest out of the benchmark's run outputs, which may hold an
unpacked copy of the benchmark itself."""
collect_ignore = ["_out"]
