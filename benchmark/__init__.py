"""The repo's benchmark: cells, traffic, metric readers and the yardstick.

Everything a later PR may not change lives here. See README.md."""
