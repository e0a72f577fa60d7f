"""perfbench: the benchmark of easyparallellibrary-tpu (see README.md)."""
