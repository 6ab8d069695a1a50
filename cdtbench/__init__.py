"""The benchmark of comfyui-distributed-tpu: the yardstick later PRs are
measured with. See ``cdtbench/README.md``."""
