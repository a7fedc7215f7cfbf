"""The benchmark's harness code: everything here is the yardstick and is
read by name from ``BENCHMARK.json``; nothing about one cell lives here."""
