"""Traffic mixes: JSON parameter files read by generator.py."""
