"""The benchmark harness: runners, references, counts and trace reduction."""
