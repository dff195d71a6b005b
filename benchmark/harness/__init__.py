"""The yardstick: what no later PR may change (see benchmark/README.md)."""
