"""The benchmark's own yardstick. Nothing in here imports the program,
except harness.py, which calls the agent's entry point."""
