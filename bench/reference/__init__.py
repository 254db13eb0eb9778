"""Plain references: straightforward implementations of what the program
computes, which import nothing of it."""
