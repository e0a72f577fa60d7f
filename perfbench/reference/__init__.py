"""Plain references, one module per architecture family; they import
nothing of the program under test."""
