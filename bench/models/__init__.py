"""Model families: the program's graph, the benchmark's weights and its
plain reference forward, from a configuration file's sizes."""
