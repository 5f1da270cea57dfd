"""Plain references, one module per architecture family.  A reference
imports nothing of the program under test."""
