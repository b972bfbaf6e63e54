"""Plain float32 references of the model families the decode server runs
(the benchmark keeps a copy of each under benchmark/references/)."""
