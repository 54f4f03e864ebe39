"""The benchmark of paddle_tpu: the yardstick later PRs are measured with.
See README.md beside this file and BENCHMARK.json at the root of the repo."""
