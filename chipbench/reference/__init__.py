"""The plain references that decide ``correct``: written from the stated
rules of each cell's kind, sharing no code with the program under test."""
