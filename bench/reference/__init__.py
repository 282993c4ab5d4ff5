"""Plain references: one module per dataset.  Each computes the answers
straight from the generator's arrays with numpy in float64, independent of
the program, compares what the timed path returned, and says for each
compared number its value and its limit."""
